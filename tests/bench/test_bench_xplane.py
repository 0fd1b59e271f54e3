"""The trace reduction, on a small trace recorded here on the CPU and
on HLO text of the engine's kernel calls."""
import glob

import numpy as np
import pytest

from bench import roofline, xplane

NTT_HLO = ('%ntt.1 = u32[8,8,128]{2,1,0:T(8,128)S(1)} custom-call(%reshape.5, '
           '%w, %ws, %q), custom_call_target="tpu_custom_call", '
           'operand_layout_constraints={u32[8,8,128]{2,1,0}, '
           'u32[4,10,8,128]{3,2,1,0}, u32[4,10,8,128]{3,2,1,0}, '
           'u32[4,1,128]{2,1,0}}')
MUL_HLO = ('%_mul_impl.34 = u32[4,1024]{1,0} custom-call(%a, %b, %q, %mu), '
           'custom_call_target="tpu_custom_call", operand_layout_constraints='
           '{u32[4,1024]{1,0}, u32[4,1024]{1,0}, u32[4,1]{1,0}, u32[4,1]{1,0}}')


def test_ntt_call_is_read_from_its_shapes():
    assert xplane.ntt_call(NTT_HLO) == (8, 1024, 4)
    assert xplane.ntt_call(MUL_HLO) is None
    assert xplane.ntt_call("fusion.3") is None


def test_union_and_attribution():
    assert xplane._union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3], [5, 9]]
    spans = [(0, 100, "query"), (10, 40, "planner"), (50, 90, "run_via_plan"),
             (60, 70, "PjitFunction(f)")]
    got = xplane._attribute(spans, [5, 20, 55, 65, 95, 120])
    assert got == ["query", "planner", "run_via_plan", "PjitFunction(f)",
                   "query", "window"]


def test_breakdown_keeps_the_top_entries():
    s = xplane.Summary(window_s=2.0, busy_s=1.5, devices=1,
                       op_s={f"op{i}": float(i) for i in range(15)},
                       kernel_s=0.5, ntt_s=0.1, ntt_bytes=10, ntt_calls=1,
                       idle_s={"run_via_plan": 0.4, "query": 0.1})
    b = s.breakdown()
    assert [k for k, _ in b["device_ops"]] == [f"op{i}" for i in range(14, 4, -1)]
    assert b["idle_gaps"] == [["run_via_plan", 0.4], ["query", 0.1]]
    assert s.idle_share == pytest.approx(0.25)


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    d = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.asarray(np.random.default_rng(0).random((256, 256)), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("query"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    return xplane.find(d)


def test_reduce_cpu_trace(cpu_trace):
    s = xplane.reduce(cpu_trace, device_prefix="/host:CPU")
    assert s.devices == 1 and s.window_s > 0
    assert 0 < s.busy_s <= s.window_s * 1.0001
    assert 0 <= s.idle_share < 1
    assert any("jit_" in k for k in s.op_s)
    assert s.kernel_s == 0 and s.ntt_calls == 0      # no Pallas kernel here
    assert sum(s.idle_s.values()) == pytest.approx(s.window_s - s.busy_s, rel=1e-6)


def test_reduce_refuses_a_trace_without_the_device(cpu_trace):
    with pytest.raises(ValueError):
        xplane.reduce(cpu_trace, device_prefix="/device:TPU:")


def test_find_wants_one_file(tmp_path):
    with pytest.raises(FileNotFoundError):
        xplane.find(str(tmp_path))


class _Ev:
    def __init__(self, name, start, dur, stats=()):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats)


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_tpu_plane_ops_named_by_program_and_instruction():
    """A TPU plane names each op by its HLO text on the "XLA Ops" line;
    the "XLA Modules" line gives the program it ran in."""
    plane = _Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Ev("jit__rotate_impl(123)", 0, 100),
                              _Ev("jit__mul_impl(456)", 200, 100)]),
        _Line("XLA Ops", [_Ev(NTT_HLO.replace("%ntt.1", "%_rotate_impl.5"), 10, 30),
                          _Ev(MUL_HLO, 210, 20)])])
    ops = xplane._device_ops(plane)
    assert [o[2] for o in ops] == [
        "jit__rotate_impl/_rotate_impl.5 custom-call u32[8,8,128]",
        "jit__mul_impl/_mul_impl.34 custom-call u32[4,1024]"]
    assert all(xplane.KERNEL in o[3] for o in ops)
