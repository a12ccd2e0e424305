"""The trace reduction: interval arithmetic on synthesised device
events, and the reading of a trace recorded here on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import trace

DEV = "/device:TPU:0"


def ev(name, start, dur, **stats):
    return trace.Event(name, float(start), float(dur), stats)


KERNEL = ('%closed_call.15 = bf16[32,16,1,128] custom-call(s32[32,23] %a), '
          'custom_call_target="tpu_custom_call"')


def synthetic():
    """Window [100, 1100) ns.  Ops: a loop in a decode program holding a
    fusion and the kernel, one prefill op, one decode op half outside
    the window.  Operations carry no program: it comes from the
    program executions that hold them."""
    ops = [ev("%while.13 = (s32[], bf16[32]) while((s32[], bf16[32]) %t)",
              100, 200),
           ev("%fusion.1 = bf16[32,2048] fusion(bf16[2] %x), kind=kLoop",
              100, 200),
           ev(KERNEL, 150, 100),
           ev("%fusion.2 = bf16[1,256] fusion(bf16[2] %y)", 500, 100),
           ev("%copy.3 = bf16[512] copy(bf16[512] %z)", 1050, 200)]
    modules = [ev("jit_paged_decode_fn(1)", 100, 200),
               ev("jit_paged_prefill_fn(2)", 500, 100),
               ev("jit_paged_decode_fn(1)", 1050, 200),
               ev("jit_paged_decode_fn(1)", 20, 50)]
    trace.attribute_modules(ops, modules)
    host = [ev(trace.WINDOW_START, 100, 0), ev("cb_decode", 90, 250),
            ev("cb_prefill", 450, 200), ev("cb_submit", 700, 300),
            ev(trace.WINDOW_END, 1100, 0)]
    return trace.Trace({DEV: modules}, {DEV: ops}, host, (100.0, 1100.0))


def test_op_label_and_program_attribution():
    assert trace.op_label(KERNEL) == ("%closed_call.15", "custom-call")
    assert trace.op_label("%copy.3 = bf16[512] copy(bf16[512] %z)") == (
        "%copy.3", "copy")
    ops = synthetic().ops[DEV]
    assert [e.stats.get("module") for e in ops] == [
        "jit_paged_decode_fn"] * 3 + ["jit_paged_prefill_fn",
                                      "jit_paged_decode_fn"]


def test_union_merges_and_clips():
    assert trace.union([(0, 10), (5, 20), (30, 40), (35, 38)], 2, 36) == [
        (2, 20), (30, 36)]
    assert trace.union([(5, 5), (50, 60)], 0, 40) == []


def test_busy_and_idle_share():
    tr = synthetic()
    # busy: [100,300) + [500,600) + [1050,1100) = 350 ns of 1000
    assert trace.busy_s(tr) == pytest.approx(350e-9)
    assert tr.window_s == pytest.approx(1000e-9)
    gaps = trace.idle_gaps(tr.ops[DEV], *tr.window)
    assert gaps == [(300.0, 500.0), (600.0, 1050.0)]


def test_program_and_op_time_in_window():
    tr = synthetic()
    t, n = trace.module_time_s(tr, lambda name: "paged_decode_fn" in name)
    # the execution at 20 ns lies before the window; 1050 starts inside
    assert (t, n) == (pytest.approx(400e-9), 2)
    t, n = trace.ops_time_s(tr, lambda e: "tpu_custom_call" in e.name)
    assert (t, n) == (pytest.approx(100e-9), 1)


def test_breakdown_names_ops_and_gaps():
    b = trace.breakdown(synthetic(), top=4)
    # the loop is left out: the operations of its body count; an
    # operation that starts in the window counts whole
    assert b["device_ops"] == [
        ["jit_paged_decode_fn/%fusion.1 fusion", pytest.approx(200e-9)],
        ["jit_paged_decode_fn/%copy.3 copy", pytest.approx(200e-9)],
        ["jit_paged_decode_fn/%closed_call.15 custom-call",
         pytest.approx(100e-9)],
        ["jit_paged_prefill_fn/%fusion.2 fusion", pytest.approx(100e-9)]]
    # longest gap 600..1050 is covered most by cb_submit (700..1000)
    assert b["idle_gaps"][0] == ["idle while host in cb_submit",
                                 pytest.approx(450e-9)]
    assert b["idle_gaps"][1][0] == "idle while host in cb_prefill"


def test_host_activity_without_span():
    assert trace.host_activity((0, 10), []) == "no harness span"


def test_read_recorded_trace(tmp_path):
    """A trace recorded on the CPU: the window markers and the harness
    spans are found on the profiler's clock, in order."""
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW_START):
        pass
    with jax.profiler.TraceAnnotation("cb_decode"):
        f(x).block_until_ready()
    with jax.profiler.TraceAnnotation(trace.WINDOW_END):
        pass
    jax.profiler.stop_trace()
    tr = trace.read(str(tmp_path))
    names = [e.name for e in tr.host]
    assert names == [trace.WINDOW_START, "cb_decode", trace.WINDOW_END]
    dec = tr.host[1]
    assert tr.window[0] <= dec.start_ns <= dec.end_ns <= tr.window[1]
    assert tr.window_s > 0
    assert trace.breakdown(tr)["device_ops"] == []   # no device plane


def test_read_without_markers_fails(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="window markers"):
        trace.read(str(tmp_path))
