from bench import work


def test_points():
    assert work.points([16384, 16384]) == 16384**2
    assert work.points((2, 3, 4)) == 24


def test_least_bytes_per_step():
    # heat reads one level and writes one: 2 x 4 B a point
    assert work.least_bytes_per_step(16384**2, 1) == 2 * 4 * 16384**2
    # wave reads two levels
    assert work.least_bytes_per_step(100, 2) == 3 * 4 * 100


def test_fused_epoch_divides_by_its_depth():
    fused = {"fused_epoch": 1, "apply": 0, "total": 1}
    unfused = {"fused_epoch": 0, "apply": 4, "total": 4}
    assert work.steps_per_kernel_call(fused, 4) == 4
    assert work.steps_per_kernel_call(unfused, 4) == 1
    assert work.steps_per_kernel_call({"fused_epoch": 0, "apply": 1}, 1) == 1
    k = work.steps_per_kernel_call(fused, 4)
    assert work.least_bytes_per_step(1000, 1, 4, k) == 2 * 4 * 1000 / 4


def test_compiled_artifact_states_its_depth():
    """The divisor as the compiled artifact states it: the fused epoch
    is one kernel per epoch of ``exchange_every`` steps."""
    from repro import api
    from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction

    u = TimeFunction(name="u", grid=Grid(shape=(64, 64)), space_order=2)
    prog = Operator(Eq(u.dt, u.laplace), dt=0.1).program
    fused = api.compile(prog, api.Target(backend="pallas", exchange_every=4,
                                         fused_epoch=True, pallas_interpret=True))
    plain = api.compile(prog, api.Target())
    assert work.steps_per_kernel_call(fused.kernel_dispatches, 4) == 4
    assert work.steps_per_kernel_call(plain.kernel_dispatches, 1) == 1
