"""Declarative pass pipelines + canonical dmp→comm lowering + IR-level
overlap: spec grammar, golden op sequences from split_overlapped_applies,
sym_name preservation, and the interpreter's comm-only contract."""
import numpy as np
import pytest

from repro.core import ir
from repro.core.dialects import comm, dmp, stencil
from repro.core.lowering import StencilInterpreter
from repro.core.passes import (
    PipelineContext,
    PipelineError,
    build_pipeline,
    decompose_stencil,
    eliminate_redundant_swaps,
    enable_comm_compute_overlap,
    lower_dmp_to_comm,
    parse_pipeline,
    run_pipeline,
    split_overlapped_applies,
    use_diagonal_exchanges,
)
from repro.core.passes.decompose import make_strategy_2d
from repro import api
from repro.api import Program, Target
from repro.frontends.oec_like import ProgramBuilder


def _jacobi_prog(shape=(32, 32)):
    p = ProgramBuilder("jacobi", shape)
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    r = p.apply(
        [t],
        lambda b, u: (u.at(-1, 0) + u.at(1, 0) + u.at(0, -1) + u.at(0, 1)) * 0.25,
    )
    p.store(r, out)
    return p.build_func()


def _box_prog(shape=(32, 32)):
    p = ProgramBuilder("box", shape)
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    r = p.apply(
        [t],
        lambda b, u: u.at(-1, -1) + u.at(1, 1) * 0.5 + u.at(-1, 1) * 0.25
        + u.at(0, 0),
    )
    p.store(r, out)
    return p.build_func()


# -------------------------------------------------------------------------
# pipeline spec grammar
# -------------------------------------------------------------------------


def test_parse_pipeline_roundtrip():
    spec = "fuse,cse,dce,decompose{grid=2x2xy,boundary=periodic},swap-elim,lower-comm"
    stages = parse_pipeline(spec)
    assert [s[0] for s in stages] == [
        "fuse", "cse", "dce", "decompose", "swap-elim", "lower-comm",
    ]
    assert stages[3][1] == {"grid": "2x2xy", "boundary": "periodic"}


def test_parse_pipeline_rejects_garbage():
    with pytest.raises(PipelineError):
        parse_pipeline("fuse,decompose{grid=2x2")
    with pytest.raises(PipelineError):
        parse_pipeline("decompose{gridnovalue}")
    with pytest.raises(PipelineError):
        build_pipeline("no-such-pass")


def test_pipeline_rejects_unknown_options():
    # misspelled/inapplicable options must not be silently ignored
    with pytest.raises(PipelineError, match="grd"):
        build_pipeline("decompose{grd=4x2}", PipelineContext())
    with pytest.raises(PipelineError, match="swap-elim"):
        build_pipeline("swap-elim{aggressive=1}")
    with pytest.raises(PipelineError, match="dims"):
        build_pipeline("decompose{dims=0x1}", PipelineContext())
    with pytest.raises(PipelineError, match="boundary"):
        build_pipeline("decompose{grid=2x2,boundary=mirror}")


@pytest.mark.parametrize(
    "spec,match",
    [
        ("fuse},cse", "unbalanced '}'"),
        ("fuse cse", "cannot parse pipeline stage 'fuse cse'"),
        ("decompose{grid=2by2}", "cannot parse grid spec '2by2'"),
        ("decompose{grid=2x2xyz}", "3 axis names for 2 grid dims"),
        ("decompose", "no grid= option and no strategy in context"),
        ("temporal-tile{k=two}", "k must be an integer, got 'two'"),
        ("temporal-tile{k=0}", "k must be >= 1, got 0"),
    ],
    ids=["unbalanced-close", "stage-name", "grid-spec", "grid-axis-names",
         "decompose-without-grid", "temporal-k-not-int", "temporal-k-zero"],
)
def test_pipeline_grammar_refusals(spec, match):
    """A malformed pipeline spec is refused while the pipeline is built,
    never half-run: the message names the stage or option at fault."""
    with pytest.raises(PipelineError, match=match):
        build_pipeline(spec, PipelineContext())


def test_grid_spec_with_axis_names():
    stages = build_pipeline("decompose{grid=2x2xy}", PipelineContext())
    func = _jacobi_prog()
    local = stages[0](func)
    (sw,) = [op for op in local.body.ops if isinstance(op, dmp.SwapOp)]
    assert sw.grid.shape == (2, 2)
    assert sw.grid.axis_names == ("x", "y")


def test_default_pipeline_always_lowers_comm():
    assert Target().pipeline_spec().endswith("lower-comm")
    spec = Target(overlap=True, diagonal=True).pipeline_spec()
    assert "diagonal" in spec and "overlap" in spec
    assert spec.index("diagonal") < spec.index("overlap")


def _prepare_local(spec, strategy=None):
    # a decomposed strategy without a mesh: IR-only inspection
    ctx = PipelineContext(strategy=strategy, boundary="periodic")
    return run_pipeline(_jacobi_prog(), spec, ctx)


def test_pipeline_timings_recorded():
    spec = Target(overlap=True).pipeline_spec()
    _, timings = _prepare_local(spec, make_strategy_2d((2, 2)))
    names = [n for n, _ in timings]
    assert names == spec.split(",")
    assert all(sec >= 0 for _, sec in timings)


# -------------------------------------------------------------------------
# canonical lowering invariants
# -------------------------------------------------------------------------


def test_lower_dmp_to_comm_preserves_sym_name():
    local = decompose_stencil(_jacobi_prog(), make_strategy_2d((2, 2)))
    lowered = lower_dmp_to_comm(local)
    assert lowered.sym_name == local.sym_name
    assert not any(isinstance(op, dmp.SwapOp) for op in lowered.body.ops)


def test_prepare_local_emits_comm_only():
    for target in (Target(), Target(overlap=True),
                   Target(diagonal=True, overlap=True)):
        local, _ = _prepare_local(
            target.pipeline_spec(), make_strategy_2d((2, 2))
        )
        assert not any(isinstance(op, dmp.SwapOp) for op in local.body.ops)
        assert any(isinstance(op, comm.ExchangeStartOp) for op in local.body.ops)


def test_interpreter_rejects_dmp_swap():
    local = decompose_stencil(_jacobi_prog(), make_strategy_2d((2, 2)))
    interp = StencilInterpreter(local, axis_sizes={}, distributed=False)
    with pytest.raises(NotImplementedError, match="dmp.swap"):
        interp(np.zeros((16, 16), np.float32), np.zeros((16, 16), np.float32))


def test_interpreter_needs_a_resolved_pallas_mode():
    """The Pallas backend runs natively or interpreted, never a guess:
    the interpreter refuses ``pallas_interpret=None`` (the ``Target``
    resolves it from the platform)."""
    local = lower_dmp_to_comm(
        decompose_stencil(_jacobi_prog(), make_strategy_2d((1, 1)))
    )
    with pytest.raises(ValueError, match="needs pallas_interpret resolved"):
        StencilInterpreter(local, axis_sizes={}, distributed=False,
                           backend="pallas")


def test_permute_pairs_shared_helper():
    # 1-axis periodic shift over 4 ranks: full cycle
    axis, pairs = comm.permute_pairs((("x", 1),), {"x": 4}, periodic=True)
    assert axis == "x"
    assert sorted(pairs) == [(0, 3), (1, 0), (2, 1), (3, 2)]
    # zero-BC drops out-of-grid destinations
    _, open_pairs = comm.permute_pairs((("x", 1),), {"x": 4}, periodic=False)
    assert (0, 3) not in open_pairs and len(open_pairs) == 3
    # diagonal: two axes linearized row-major
    axes, dpairs = comm.permute_pairs(
        (("x", 1), ("y", 1)), {"x": 2, "y": 2}, periodic=True
    )
    assert axes == ("x", "y")
    assert len(dpairs) == 4


# -------------------------------------------------------------------------
# split_overlapped_applies: golden op sequences
# -------------------------------------------------------------------------


def _overlap_split(func, grid=(2, 2), diagonal=False):
    local = decompose_stencil(func, make_strategy_2d(grid), boundary="periodic")
    eliminate_redundant_swaps(local)
    if diagonal:
        use_diagonal_exchanges(local)
    assert enable_comm_compute_overlap(local) == 1
    split = split_overlapped_applies(local)
    ir.verify_module(split)
    return split


def test_split_golden_sequence_star_concurrent():
    split = _overlap_split(_jacobi_prog())
    names = [op.name for op in split.body.ops]
    assert names == (
        ["stencil.load", "comm.halo_pad"]
        + ["comm.exchange_start"] * 4   # 4 face exchanges, one round
        + ["stencil.apply"]             # interior, between starts and wait
        + ["comm.wait"]
        + ["stencil.apply"] * 4         # onion-peel boundary frames
        + ["stencil.combine", "stencil.store", "func.return"]
    ), names


def test_split_golden_sequence_box_sequential():
    split = _overlap_split(_box_prog())
    names = [op.name for op in split.body.ops]
    # sequential corner-forwarding: round 1 (axis 0) overlaps the interior,
    # round 2 (axis 1) chains off round 1's wait
    assert names == (
        ["stencil.load", "comm.halo_pad"]
        + ["comm.exchange_start"] * 2   # round 1: axis-0 faces
        + ["stencil.apply"]             # interior
        + ["comm.wait"]
        + ["comm.exchange_start"] * 2   # round 2: axis-1 faces (forwarded)
        + ["comm.wait"]
        + ["stencil.apply"] * 4
        + ["stencil.combine", "stencil.store", "func.return"]
    ), names


def test_split_golden_sequence_box_diagonal():
    split = _overlap_split(_box_prog(), diagonal=True)
    names = [op.name for op in split.body.ops]
    # diagonal rewrite: concurrent faces + corners, all in one round
    n_starts = names.count("comm.exchange_start")
    assert n_starts == 8  # 4 faces + 4 corners on a 2x2 grid
    assert names.index("stencil.apply") > names.index("comm.exchange_start")
    assert names.index("stencil.apply") < names.index("comm.wait")
    assert names.count("comm.wait") == 1


def test_split_part_attributes_and_bounds():
    split = _overlap_split(_jacobi_prog())
    applies = [op for op in split.body.ops if isinstance(op, stencil.ApplyOp)]
    parts = [op.attributes["part"].value for op in applies]
    assert parts == ["interior"] + ["frame"] * 4
    interior = applies[0]
    # jacobi halo 1: interior = local core (16x16) shrunk by 1 per side
    assert interior.result_bounds.shape == (14, 14)
    (combine,) = [op for op in split.body.ops if isinstance(op, stencil.CombineOp)]
    assert combine.result_bounds.shape == (16, 16)
    # parts tile the result exactly
    covered = sum(
        int(np.prod(p.type.bounds.shape)) for p in combine.operands
    )
    assert covered == 16 * 16


def test_split_skips_ineligible_swaps():
    # a swap whose result is consumed by two applies must not be split
    p = ProgramBuilder("two", (16, 16))
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    a = p.apply([t], lambda b, u: (u.at(-1, 0) + u.at(1, 0)) * 0.5)
    c = p.apply([t], lambda b, u: (u.at(0, -1) + u.at(0, 1)) * 0.5)
    s = p.apply([a, c], lambda b, x, y: x.at(0, 0) + y.at(0, 0))
    p.store(s, out)
    func = p.build_func()
    local = decompose_stencil(func, make_strategy_2d((2, 2)))
    eliminate_redundant_swaps(local)
    n_swaps = sum(1 for op in local.body.ops if isinstance(op, dmp.SwapOp))
    enable_comm_compute_overlap(local)
    split = split_overlapped_applies(local)
    remaining = sum(1 for op in split.body.ops if isinstance(op, dmp.SwapOp))
    # declined swaps are untagged, so lower-comm handles them silently
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        lowered = lower_dmp_to_comm(split)
    assert not any(isinstance(op, dmp.SwapOp) for op in lowered.body.ops)
    assert remaining <= n_swaps
    ir.verify_module(lowered)


def test_split_is_identity_when_nothing_tagged():
    local = decompose_stencil(_jacobi_prog(), make_strategy_2d((2, 2)))
    assert split_overlapped_applies(local) is local


def test_lower_comm_warns_on_unsplit_overlap_tag():
    # overlap-tag without split-overlap: the tag must not vanish silently
    local = decompose_stencil(_jacobi_prog(), make_strategy_2d((2, 2)))
    eliminate_redundant_swaps(local)
    enable_comm_compute_overlap(local)
    with pytest.warns(UserWarning, match="overlap-tagged"):
        lower_dmp_to_comm(local)


# -------------------------------------------------------------------------
# temporal_tile: golden op sequences (one exchange per epoch)
# -------------------------------------------------------------------------


def _tiled(func, spec, boundary="periodic"):
    ctx = PipelineContext(
        strategy=make_strategy_2d((2, 2)), boundary=boundary
    )
    out, _ = run_pipeline(func, spec, ctx)
    return out


def test_temporal_tile_golden_sequence():
    """k=2 epoch of the star stencil: ONE deep exchange (sequential —
    S∘S has a diamond footprint, so corners must be forwarded), then the
    two cloned applies, then the store."""
    split = _tiled(
        _jacobi_prog(),
        "decompose,swap-elim,temporal-tile{k=2},lower-comm",
    )
    names = [op.name for op in split.body.ops]
    assert names == (
        ["stencil.load", "comm.halo_pad"]
        + ["comm.exchange_start"] * 2 + ["comm.wait"]   # axis-0 round
        + ["comm.exchange_start"] * 2 + ["comm.wait"]   # axis-1 (forwarded)
        + ["stencil.apply"] * 2                         # step 1 grown, step 2 core
        + ["stencil.store", "func.return"]
    ), names


def test_temporal_tile_scales_halo_extents():
    local = decompose_stencil(
        _jacobi_prog(), make_strategy_2d((2, 2)), boundary="periodic"
    )
    eliminate_redundant_swaps(local)
    from repro.core.passes import temporal_tile

    tiled = temporal_tile(local, 4)
    ir.verify_module(tiled)
    (swap,) = [op for op in tiled.body.ops if isinstance(op, dmp.SwapOp)]
    assert swap.halo_widths() == ((4, 4), (4, 4))  # per-step 1 × k=4
    applies = [op for op in tiled.body.ops if isinstance(op, stencil.ApplyOp)]
    assert [a.attributes["epoch_step"].value for a in applies] == [1, 2, 3, 4]
    # local core 16×16; step j computes core + (k-j) redundant frame
    assert [a.result_bounds.shape for a in applies] == [
        (22, 22), (20, 20), (18, 18), (16, 16)
    ]


def test_temporal_tile_overlap_split_still_applied():
    """temporal-tile composes with the overlap split: step 1's interior
    (clipped to the pre-exchange core minus its reads) overlaps the deep
    exchange; frames + later steps run after the waits."""
    split = _tiled(
        _jacobi_prog(),
        "decompose,swap-elim,temporal-tile{k=2},overlap,lower-comm",
    )
    names = [op.name for op in split.body.ops]
    first_apply = names.index("stencil.apply")
    assert names.index("comm.exchange_start") < first_apply
    assert first_apply < names.index("comm.wait"), names
    assert "stencil.combine" in names
    applies = [op for op in split.body.ops if isinstance(op, stencil.ApplyOp)]
    interior = applies[0]
    assert interior.attributes["part"].value == "interior"
    # the interior may not read exchanged halo points: core 16² shrunk by
    # the step-1 access extent, NOT the grown 18² result shrunk by 1
    assert interior.result_bounds.shape == (14, 14)
    (combine,) = [op for op in split.body.ops if isinstance(op, stencil.CombineOp)]
    assert combine.result_bounds.shape == (18, 18)  # step 1 output, grown
    covered = sum(int(np.prod(p.type.bounds.shape)) for p in combine.operands)
    assert covered == 18 * 18  # interior + frames tile the grown domain
    # the final (core) step runs on the combined value, after every wait
    assert applies[-1].result_bounds.shape == (16, 16)


def test_temporal_tile_zero_bc_masks_in_sequence():
    split = _tiled(
        _jacobi_prog(),
        "decompose,swap-elim,temporal-tile{k=2},lower-comm",
        boundary="zero",
    )
    names = [op.name for op in split.body.ops]
    # exactly one mask: the grown step-1 result, re-clamped to the
    # physical domain before step 2 reads it
    assert names.count("comm.boundary_mask") == 1
    assert names.index("comm.boundary_mask") > names.index("stencil.apply")
    assert names.index("comm.boundary_mask") < len(names) - 1 - names[::-1].index(
        "stencil.apply"
    )


def test_temporal_tile_via_spec_matches_flag_surface():
    from repro.api import Target

    spec = Target(exchange_every=4).pipeline_spec()
    assert "temporal-tile{k=4}" in spec
    assert spec.index("swap-elim") < spec.index("temporal-tile")
    assert spec.index("temporal-tile") < spec.index("lower-comm")
    parsed = parse_pipeline(spec)
    assert ("temporal-tile", {"k": "4"}) in parsed


def test_fuse_epoch_golden_sequence():
    """fuse-epoch-kernel after lower-comm: the k=2 epoch's two applies
    (and the zero-BC re-masking between them) collapse into exactly ONE
    region-bearing stencil.fused_epoch op — the op the pallas backend
    turns into a single kernel dispatch."""
    fused = _tiled(
        _jacobi_prog(),
        "decompose,swap-elim,temporal-tile{k=2},lower-comm,fuse-epoch-kernel",
        boundary="zero",
    )
    ir.verify_module(fused)
    names = [op.name for op in fused.body.ops]
    assert names.count("stencil.fused_epoch") == 1
    assert "stencil.apply" not in names
    assert "comm.boundary_mask" not in names
    # comm stays outside the kernel: exchange before, store after
    assert names.index("comm.wait") < names.index("stencil.fused_epoch")
    assert names.index("stencil.fused_epoch") < names.index("stencil.store")
    (fop,) = [
        op for op in fused.body.ops
        if isinstance(op, stencil.FusedEpochOp)
    ]
    inner = [op.name for op in fop.body.ops]
    assert inner == [
        "stencil.apply",
        "comm.boundary_mask",
        "stencil.apply",
        "stencil.fused_yield",
    ], inner
    assert fop.k == 2
    # the epoch's escape is the core-bounds step-2 result the store reads
    (res,) = fop.results
    assert res.type.bounds.shape == (16, 16)


def test_pipeline_overlap_semantics_single_device():
    rng = np.random.default_rng(11)
    u0 = rng.standard_normal((24, 24)).astype(np.float32)
    out0 = np.zeros_like(u0)
    prog = Program(_box_prog((24, 24)), boundary="periodic")
    base = api.compile(prog, Target())(u0, out0)
    via_spec = api.compile(
        prog,
        Target(pipeline="fuse,cse,dce,decompose,swap-elim,overlap,lower-comm"),
    )(u0, out0)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(via_spec))
