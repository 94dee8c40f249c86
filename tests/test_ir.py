"""Unit tests for the SSA+regions IR infrastructure (repro.core.ir) and
the stencil dialect invariants — the paper's §3 foundations."""
import pytest

from repro.core import ir
from repro.core.builder import build_apply
from repro.core.dialects import stencil


def _jacobi_func(shape=(8, 8)):
    core = stencil.Bounds.from_shape(shape)
    func = ir.FuncOp("jacobi", [stencil.FieldType(core), stencil.FieldType(core)])
    load = func.body.add_op(stencil.LoadOp(func.body.args[0]))

    def body(b, u):
        return (u.at(-1, 0) + u.at(1, 0) + u.at(0, -1) + u.at(0, 1)) * 0.25

    apply_op = build_apply(func.body, [load.results[0]], core, body)
    func.body.add_op(stencil.StoreOp(apply_op.results[0], func.body.args[1], core))
    func.body.add_op(ir.ReturnOp([]))
    return func, apply_op


def test_ssa_def_use_chains():
    func, apply_op = _jacobi_func()
    load = next(op for op in func.body.ops if isinstance(op, stencil.LoadOp))
    # the load result is used exactly once (by the apply)
    assert load.results[0].num_uses == 1
    assert apply_op.operands[0] is load.results[0]
    # apply result used by the store
    assert apply_op.results[0].num_uses == 1


def test_verifier_accepts_wellformed():
    func, _ = _jacobi_func()
    ir.verify_module(func)  # must not raise


def test_verifier_rejects_store_out_of_bounds():
    core = stencil.Bounds.from_shape((8, 8))
    big = stencil.Bounds.from_shape((16, 16))
    func = ir.FuncOp("bad", [stencil.FieldType(core), stencil.FieldType(core)])
    load = func.body.add_op(stencil.LoadOp(func.body.args[0]))

    def body(b, u):
        return u.at(0, 0)

    apply_op = build_apply(func.body, [load.results[0]], core, body)
    # store with bounds exceeding the field
    func.body.add_op(stencil.StoreOp(apply_op.results[0], func.body.args[1], big))
    func.body.add_op(ir.ReturnOp([]))
    with pytest.raises(Exception):
        ir.verify_module(func)


_CORE = stencil.Bounds.from_shape((8, 8))
_BIG = stencil.Bounds.from_shape((16, 16))
_INNER = stencil.Bounds((1, 1), (7, 7))


def _field_func(*bounds):
    return ir.FuncOp("probe", [stencil.FieldType(b) for b in bounds])


def _load(bounds=None, field=_CORE):
    func = _field_func(field)
    return func.body.add_op(stencil.LoadOp(func.body.args[0], bounds))


def _centre(b, u):
    return u.at(0, 0)


def _load_outside_field():
    return _load(bounds=_BIG)


def _store_outside_temp():
    func = _field_func(_BIG)
    load = func.body.add_op(stencil.LoadOp(func.body.args[0], _CORE))
    return stencil.StoreOp(load.results[0], func.body.args[0], _BIG)


def _apply_arg_count():
    op = stencil.ApplyOp([_load().results[0]], _CORE)
    op.set_operands([])
    return op


def _apply_arg_type():
    op = build_apply(ir.Block([]), [_load().results[0]], _CORE, _centre)
    op.replace_operand(0, _load(field=_BIG).results[0])
    return op


def _apply_without_return():
    return stencil.ApplyOp([_load().results[0]], _CORE)


def _apply_return_arity():
    return build_apply(ir.Block([]), [_load().results[0]], _CORE,
                       _centre, n_results=2)


def _apply_reads_past_halo():
    return build_apply(ir.Block([]), [_load(bounds=_INNER).results[0]],
                       stencil.Bounds((2, 2), (6, 6)),
                       lambda b, u: u.at(-2, 0))


def _combine_part_outside():
    return stencil.CombineOp([_load().results[0]], _INNER)


@pytest.mark.parametrize(
    "make,match",
    [
        (_load_outside_field, "stencil.load reads .* outside field bounds"),
        (_store_outside_temp, "stencil.store range .* outside temp bounds"),
        (_apply_arg_count, "region arg count != operand count"),
        (_apply_arg_type, "region arg type .* != operand type"),
        (_apply_without_return, "must end in stencil.return"),
        (_apply_return_arity,
         "stencil.return arity != stencil.apply result arity"),
        (_apply_reads_past_halo,
         r"accesses .* of operand 0 .*\(halo missing\?\)"),
        (_combine_part_outside, "stencil.combine part .* outside result bounds"),
    ],
    ids=["load-bounds", "store-temp-bounds", "apply-arg-count",
         "apply-arg-type", "apply-return", "apply-return-arity",
         "apply-halo", "combine-part"],
)
def test_verifier_rejects_malformed_stencil_op(make, match):
    """Each stencil-dialect invariant a frontend or a pass could break is
    refused by the op's verifier with a message naming it."""
    with pytest.raises(ir.VerificationError, match=match):
        make().verify()


def test_verifier_rejects_use_before_definition():
    func = _field_func(_CORE, _CORE)
    load = stencil.LoadOp(func.body.args[0])
    build_apply(func.body, [load.results[0]], _CORE, _centre)
    func.body.add_op(load)
    func.body.add_op(ir.ReturnOp([]))
    with pytest.raises(ir.VerificationError,
                       match="of stencil.apply used before definition"):
        ir.verify_module(func)


def test_access_extents_reflect_offsets():
    _, apply_op = _jacobi_func()
    exts = apply_op.access_extents()
    lo, hi = exts[0]
    assert lo == (-1, -1)
    assert hi == (1, 1)


def test_bounds_algebra():
    b = stencil.Bounds.from_shape((10, 20))
    assert b.shape == (10, 20)
    assert b.rank == 2
    g = b.grow((2, 1), (2, 1))
    assert g.lb == (-2, -1) and g.ub == (12, 21)
    assert g.contains(b)
    assert not b.contains(g)


def test_value_replacement_updates_uses():
    func, apply_op = _jacobi_func()
    core = stencil.Bounds.from_shape((8, 8))
    # splice a second load and redirect the apply to it
    load2 = stencil.LoadOp(func.body.args[0])
    func.body.insert_op_before(load2, apply_op)
    old = apply_op.operands[0]
    old.replace_all_uses_with(load2.results[0])
    assert apply_op.operands[0] is load2.results[0]
    assert old.num_uses == 0
    ir.verify_module(func)


def test_clone_is_deep_and_disconnected():
    func, _ = _jacobi_func()
    new = ir.FuncOp(func.sym_name, [a.type for a in func.body.args])
    vmap = dict(zip(func.body.args, new.body.args))
    for op in func.body.ops:
        new.body.add_op(op.clone_into(vmap))
    ir.verify_module(new)
    assert len(new.body.ops) == len(func.body.ops)
    # mutating the clone leaves the original intact
    n_before = len(func.body.ops)
    new.body.ops[-1].erase()
    assert len(func.body.ops) == n_before


def test_printer_emits_mlir_like_text():
    func, _ = _jacobi_func()
    text = ir.print_module(func)
    for needle in ("stencil.load", "stencil.apply", "stencil.store", "stencil.access"):
        assert needle in text, text


def test_multi_result_apply():
    core = stencil.Bounds.from_shape((6, 6))
    func = ir.FuncOp(
        "multi",
        [stencil.FieldType(core), stencil.FieldType(core), stencil.FieldType(core)],
    )
    load = func.body.add_op(stencil.LoadOp(func.body.args[0]))

    def body(b, u):
        return u.at(0, 0) * 2.0, u.at(0, 0) + 1.0

    apply_op = build_apply(func.body, [load.results[0]], core, body, n_results=2)
    assert len(apply_op.results) == 2
    func.body.add_op(stencil.StoreOp(apply_op.results[0], func.body.args[1], core))
    func.body.add_op(stencil.StoreOp(apply_op.results[1], func.body.args[2], core))
    func.body.add_op(ir.ReturnOp([]))
    ir.verify_module(func)
