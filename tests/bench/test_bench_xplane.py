"""The trace reduction, on a small trace recorded here on the CPU, on a
two-chip trace written here in the XSpace format a chip writes, and on
HLO text of the engine's kernel calls; and the readers of the counters,
scopes and spans it gives by name."""
import glob
import os

import numpy as np
import pytest

from bench import harness, roofline, xplane

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

    @jax.jit
    def f(x):
        with jax.named_scope("he.keyswitch"):
            return jnp.sin(x) @ x.T
    x = jnp.asarray(np.random.default_rng(0).random((256, 256)), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("nshedb.query"):
                with jax.profiler.TraceAnnotation("nshedb.he:mul", blocks=1):
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


def test_span_s_on_the_cpu_trace(cpu_trace):
    """Host seconds per nshedb.* span: a nested span counts in the span
    around it; a CPU trace names no device op by its scope."""
    s = xplane.reduce(cpu_trace, device_prefix="/host:CPU")
    assert set(s.span_s) == {"nshedb.query", "nshedb.he:mul"}
    assert 0 < s.span_s["nshedb.he:mul"] <= s.span_s["nshedb.query"] <= s.window_s
    assert s.scope_s == {} and s.collective_s == 0 and s.collective_bytes == 0


@pytest.mark.parametrize("text,nbytes", [
    ("%all-reduce.3 = s64[2,4,128]{2,1,0} all-reduce(%p), replica_groups={{0,1}}, "
     "to_apply=%add", 8 * 2 * 4 * 128),
    ("%all-gather-start.1 = (u32[2,128]{1,0}, u32[8,128]{1,0}) "
     "all-gather-start(%x), dimensions={0}", 0),
    ("%all-gather-done.1 = u32[8,128]{1,0:T(8,128)} all-gather-done("
     "%all-gather-start.1)", 4 * 8 * 128),
    ("%reduce-scatter.2 = f32[16]{0} reduce-scatter(%y), dimensions={0}", 64),
    ("%collective-permute-done = bf16[4,4]{1,0} collective-permute-done(%s)", 32),
    ("%all-to-all = (s32[4]{0}, s32[4]{0}) all-to-all(%a, %b)", 32),
    ("%fusion.7 = s64[2,4,128]{2,1,0} fusion(%all-reduce.3), kind=kLoop", None),
    ("%all-reduce-scatter-fusion = u32[8]{0} fusion(%z), kind=kLoop", None),
    (NTT_HLO, None),
    ("fusion.3", None),
])
def test_collective_classifier(text, nbytes):
    """Collectives by HLO opcode; an asynchronous pair counts its bytes
    once, on the -done half."""
    assert xplane.collective(text) == nbytes


def test_scopes_on_an_ops_path():
    assert xplane.scopes("jit(_rotate_impl)/he.keyswitch/he.ntt/pallas_call") == [
        "he.keyswitch", "he.ntt"]
    assert xplane.scopes("jit(f)/he.tensor/while/body/he.tensor/mul") == ["he.tensor"]
    assert xplane.scopes("ksk_b") == []
    assert xplane.scopes("", "%he.ntt.1 = u32[8,8,128]{2,1,0} custom-call(%a)") == [
        "he.ntt"]
    assert xplane.scopes("", MUL_HLO) == []


def _chip_trace(path):
    """Two device planes and the host thread as a chip's profiler writes
    them: ops on "XLA Ops", each op's path in its metadata's tf_op stat;
    times in ns.  Returns the path."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()

    def plane(name, lines):
        pl = space.planes.add(id=len(space.planes), name=name)
        pl.stat_metadata[1].id, pl.stat_metadata[1].name = 1, "tf_op"
        meta = {}
        for ln_name, events in lines:
            ln = pl.lines.add(id=len(pl.lines) + 1, name=ln_name, timestamp_ns=1000)
            for text, op_name, start, end in events:
                if text not in meta:
                    mid = meta[text] = len(meta) + 1
                    md = pl.event_metadata[mid]
                    md.id, md.name = mid, text
                    if op_name:
                        md.stats.add(metadata_id=1, str_value=op_name + ":")
                ln.events.add(metadata_id=meta[text], offset_ps=start * 1000,
                              duration_ps=(end - start) * 1000)

    plane("/host:CPU", [("python", [
        ("window", "", 0, 1_000_000),
        ("nshedb.query", "", 100_000, 900_000),
        ("nshedb.stage:where", "", 200_000, 600_000),
        ("nshedb.he:mul", "", 300_000, 400_000),
        ("nshedb.he:mul", "", 500_000, 550_000),
        ("nshedb.query", "", 950_000, 1_200_000)])])
    ntt = NTT_HLO.replace("%ntt.1", "%he.ntt.1")
    done = "%all-reduce-done.1 = s64[2,4,128]{2,1,0} all-reduce-done(%all-reduce-start.1)"
    start = "%all-reduce-start.1 = s64[2,4,128]{2,1,0} all-reduce-start(%p)"
    for chip, extra in ((0, [(ntt, "jit(_rotate_impl)/he.keyswitch/he.ntt/pallas_call",
                              100_000, 300_000),
                             ("%fusion.2 = u32[4,1024]{1,0} fusion(%a), kind=kLoop",
                              "jit(_mul_impl)/he.tensor/he.hps/mul", 400_000, 500_000),
                             ("%convert.1 = u32[4,1024]{1,0} convert(%k)", "ksk_b",
                              500_000, 600_000),
                             (ntt.replace("he.ntt.1", "he.ntt.2"), "", 600_000, 650_000)]),
                        (1, [])):
        plane(f"/device:TPU:{chip}", [("XLA Ops", extra + [
            (start, "jit(_fold_psum)/psum", 700_000, 710_000),
            (done, "jit(_fold_psum)/psum", 750_000, 800_000)])])
    with open(path, "wb") as f:
        f.write(space.SerializeToString())
    return str(path)


def test_scope_span_and_collectives_on_a_chip_trace(tmp_path):
    s = xplane.reduce(_chip_trace(tmp_path / "t.xplane.pb"))
    assert s.devices == 2 and s.window_s == pytest.approx(1e-3)
    # an op counts toward every he.* scope on its path; the unscoped
    # Pallas call toward the scope it is named after; mean over chips
    assert s.scope_s == pytest.approx({
        "he.keyswitch": 200e-6 / 2, "he.ntt": 250e-6 / 2,
        "he.tensor": 100e-6 / 2, "he.hps": 100e-6 / 2})
    assert s.span_s == pytest.approx({
        "nshedb.query": 850e-6, "nshedb.stage:where": 400e-6,
        "nshedb.he:mul": 150e-6})
    assert s.collective_s == pytest.approx(60e-6)
    assert s.collective_bytes == 8 * 2 * 4 * 128
    # the existing numbers are read as before from the same trace
    assert s.busy_s == pytest.approx((510e-6 + 60e-6) / 2)
    assert s.ntt_calls == 1 and s.kernel_s == pytest.approx(250e-6 / 2)


def _rec(ops, trace=None, peaks=None):
    return harness.RunRecord(
        cell="li32k.q1-cold", queries=2, window_s=1.0,
        ops=harness.window_ops({k: 0 for k in ops}, ops), window_compiles=[],
        admit_s=None, trace=trace, memory_peak_bytes=0, peaks=peaks)


def _read(name, rec):
    root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    return harness.read_metric(os.path.join(root, "bench", "metrics"), name, rec)


def _summary(scope_s, devices=1):
    return xplane.Summary(window_s=2.0, busy_s=1.5, devices=devices, op_s={},
                          kernel_s=0.5, ntt_s=0.1, ntt_bytes=10, ntt_calls=1,
                          idle_s={}, scope_s=scope_s)


def test_window_ops_keep_every_counter_and_maxima():
    ops = harness.window_ops({"mul": 3, "dispatches": 10, "max_depth": 4},
                             {"mul": 5, "dispatches": 17, "max_depth": 6})
    assert ops == {"mul": 2, "dispatches": 7, "max_depth": 6}
    assert ops["a_counter_the_program_lacks"] == 0


def test_dispatches_per_query():
    assert _read("dispatches_per_query", _rec({"dispatches": 7568})) == 3784
    assert _read("dispatches_per_query", _rec({"dispatches": 0})) is None


def test_keyswitch_ms():
    peaks = {"hbm_bytes_per_s": 819e9}
    tr = _summary({"he.keyswitch": 3.066, "he.ntt": 1.0})
    got = _read("keyswitch_ms", _rec({"mul": 100, "rotate": 200}, tr, peaks))
    assert got == pytest.approx(1e3 * 3.066 / 300)
    # on a data mesh the counters charge every chip's blocks: the time
    # is summed over the chips, not averaged
    mesh = _summary({"he.keyswitch": 3.066 / 4}, devices=4)
    assert _read("keyswitch_ms", _rec({"mul": 100, "rotate": 200}, mesh, peaks)) == (
        pytest.approx(1e3 * 3.066 / 300))
    # not on a chip (a CPU trace names no scope), or no switch: nothing
    assert _read("keyswitch_ms", _rec({"mul": 100}, _summary({}))) is None
    assert _read("keyswitch_ms", _rec({"mul": 0, "rotate": 0}, tr, peaks)) is None


def test_keyswitch_ms_raises_where_no_time_carries_the_scope():
    """Key switches ran on a chip and no device time is under
    he.keyswitch: a renamed scope must not silence the metric."""
    tr = _summary({"he.ntt": 1.0, "he.kswitch": 3.0})
    with pytest.raises(RuntimeError, match="he.keyswitch"):
        _read("keyswitch_ms", _rec({"mul": 1, "rotate": 2}, tr,
                                   {"hbm_bytes_per_s": 819e9}))
