"""Benchmark of the stencil stack on the chip: cells, traffic, metrics.

Everything that belongs to one configuration, traffic mix, metric or
equation is a file of its own, found by name (``registry``).  Run a cell
with ``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout.
"""
