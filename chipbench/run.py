#!/usr/bin/env python3
"""Serve one benchmark cell on the chip this process runs on.

    python3 chipbench/run.py --workload olmo-1b.conversation-closed --seed 7 \
        --seconds 51 --trace 0

The cell (``BENCHMARK.json``) names a configuration and a traffic mix.
The run makes the weights on the device from ``--seed``, builds the
program's paged ``Engine`` behind an ``InProcessBackend`` and a
``PagedLLMScheduler``, warms up the shapes the mix uses (set-up ends
here), runs the mix's closed loop of clients for a lead-in and then
for the measured window, and keeps it running until every request sent
in the window has its first token.  Then it frees the program's state,
checks a sample of the served requests against the plain reference,
and prints the result as the last line of standard output.  ``--trace 1`` records the window
with the JAX profiler and reports the per-layer metrics instead of the
end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits
with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is timed from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List  # noqa: E402

import numpy as np  # noqa: E402

# libtpu logs under /tmp unless told otherwise: keep them in TMPDIR
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))
ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import check, costs, peaks, spec, trace  # noqa: E402
from chipbench import traffic as traffic_mod  # noqa: E402
from chipbench import weights as weights_mod  # noqa: E402

NO_CHIP = 3
# model settings the configuration file states and the program's
# ModelConfig must hold (a difference is applied only where the file
# lists the key under "reduced")
MODEL_KEYS = ("num_layers", "d_model", "d_ff", "vocab_size", "num_heads",
              "num_kv_heads", "head_dim", "v_head_dim", "q_lora", "kv_lora",
              "d_nope", "d_rope", "norm", "norm_eps", "act", "gated_mlp",
              "tie_embeddings", "rope_theta", "embed_scale", "residual_scale",
              "compute_dtype", "kv_cache_dtype")
FIRST_TOKEN_WAIT_S = 60.0     # after the window: for requests sent in it
SLO_MS = 3.6e6                # deadlines order prefill by arrival only


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, flush=True)


def _same(a, b) -> bool:
    if isinstance(a, float) or isinstance(b, float):
        return (a is not None and b is not None
                and math.isclose(float(a), float(b), rel_tol=1e-12))
    return a == b


def model_config(conf: Dict):
    """The program's ModelConfig for ``conf``: the registered
    architecture, with the keys the file lists under ``reduced`` set to
    the file's values.  Any other difference is an error."""
    from repro.configs import get_config
    mcfg = get_config(conf["arch"])
    changes = {}
    for key in MODEL_KEYS:
        if key not in conf:
            continue
        have, want = getattr(mcfg, key), conf[key]
        if not _same(have, want):
            if key not in conf.get("reduced", {}):
                raise ValueError(f"{conf['name']}: the program's {key} is "
                                 f"{have!r}, the configuration file says "
                                 f"{want!r}")
            changes[key] = want
    mcfg = mcfg.with_(**changes)
    mixers = {s.mixer for s in mcfg.pattern}
    if mixers != {conf["mixer"]}:
        raise ValueError(f"{conf['name']}: layers {mixers}, file says "
                         f"{conf['mixer']!r}")
    return mcfg


@dataclasses.dataclass
class Sent:
    spec: traffic_mod.RequestSpec
    handle: object
    sent: float


class Recorder:
    """Host-side facts of each engine call, for the per-layer metrics:
    the decode steps (rows, keys attended) and the prefill chunks
    (tokens computed, position of the first), with their start times.
    Each call also runs inside a profiler annotation ``cb_decode`` or
    ``cb_prefill``."""

    def __init__(self, engine):
        import jax
        self.decode: List[tuple] = []
        self.prefill: List[tuple] = []
        ann = jax.profiler.TraceAnnotation
        decode_fn, prefill_fn = engine.decode_step_batch, engine.prefill_chunk

        def decode_step_batch(seqs):
            t = time.monotonic()
            keys = sum(s.pos + 1 for s in seqs)
            with ann("cb_decode"):
                out = decode_fn(seqs)
            self.decode.append((t, len(seqs), keys))
            return out

        def prefill_chunk(seq, **kw):
            t = time.monotonic()
            before = seq.prefill_pos
            with ann("cb_prefill"):
                done = prefill_fn(seq, **kw)
            start = max(before, seq.shared_prefix_len if before == 0 else 0)
            after = seq.prompt_len if done else seq.prefill_pos
            if after > start:
                self.prefill.append((t, after - start, start))
            return done

        engine.decode_step_batch = decode_step_batch
        engine.prefill_chunk = prefill_chunk


async def drive(sched, schedule, mix: Dict, seconds: float, hooks) -> Dict:
    """Run the mix's closed loop: each client sends its next request
    when its last one completes, through the lead-in, the window, and
    until every request sent in the window has its first token; then
    stop.  ``hooks`` is called a second before the window (``before``)
    and at its edges (``start``, ``end``)."""
    from repro.serving.scheduler import SamplingParams
    sent: List[Sent] = []
    await sched.start()
    t_base = time.monotonic()
    w0 = t_base + float(mix["lead_in_s"])
    w1 = w0 + seconds
    stop = asyncio.Event()

    async def wait_or_stop(delay: float) -> None:
        if delay > 0:
            try:
                await asyncio.wait_for(stop.wait(), delay)
            except asyncio.TimeoutError:
                pass

    async def client(i: int):
        while not stop.is_set():
            spec = schedule.take(i)
            h = sched.submit(spec.prompt, SamplingParams(
                max_new_tokens=spec.max_new_tokens, stream=True))
            sent.append(Sent(spec, h, time.monotonic()))
            await asyncio.gather(h.future, return_exceptions=True)

    tasks = [asyncio.ensure_future(client(i))
             for i in range(int(mix["clients"]))]
    await wait_or_stop(w0 - 1.0 - time.monotonic())
    hooks.before()
    await wait_or_stop(w0 - time.monotonic())
    t_w0 = time.monotonic()
    hooks.start()
    await wait_or_stop(w1 - time.monotonic())
    t_w1 = time.monotonic()
    hooks.end()

    def pending_first():
        return [s for s in sent if t_w0 <= s.sent < t_w1
                and not s.handle.request.first_token_t
                and not s.handle.request.is_terminal]

    limit = t_w1 + FIRST_TOKEN_WAIT_S
    while pending_first() and time.monotonic() < limit:
        await asyncio.sleep(0.02)
    # requests still in flight are cut here: the sample that is checked
    # comes from those that finished
    stop.set()
    t_stop = time.monotonic()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    await sched.stop(drain=False)
    from repro.serving.scheduler import EventType
    events = {}
    for s in sent:          # every request is terminal: FINISHED is queued
        evs = [await s.handle.request.next_event()]
        while evs[-1].type is not EventType.FINISHED:
            evs.append(await s.handle.request.next_event())
        events[id(s)] = evs
    return {"sent": sent, "events": events, "w0": t_w0, "w1": t_w1,
            "t_stop": t_stop}


def request_rows(run: Dict) -> List[Dict]:
    """One dict per request: its times on the host clock, its tokens'
    times, and what it served.  A request that never started or never
    had its first token counts as waiting until the run stopped
    offering load."""
    from repro.serving.scheduler import EventType, RequestState
    rows = []
    end = run["t_stop"]
    for s in run["sent"]:
        req = s.handle.request
        toks = [e.t for e in run["events"][id(s)]
                if e.type in (EventType.FIRST_TOKEN, EventType.TOKEN)]
        ok = req.state is RequestState.COMPLETED
        out = np.asarray(req.output) if ok else np.zeros((0,), np.int32)
        rows.append({
            "index": s.spec.index, "sent": s.sent,
            "started": req.started_t or end,
            "first": req.first_token_t or end,
            "token_times": toks, "finished": ok,
            # failed by the system, not cut when the run stopped
            "failed": (req.state is RequestState.FAILED
                       and req.finished_t < end),
            "prompt_len": len(s.spec.prompt),
            "max_new_tokens": s.spec.max_new_tokens,
            "n_out": max(0, len(out) - len(s.spec.prompt)),
            "output": out})
    return rows


def end_to_end(rows: List[Dict], w0: float, w1: float) -> Dict[str, float]:
    ttft = [r["first"] - r["sent"] for r in rows if w0 <= r["sent"] < w1]
    gaps, tokens = [], 0
    for r in rows:
        ts = r["token_times"]
        tokens += sum(w0 <= t < w1 for t in ts)
        gaps += [b - a for a, b in zip(ts, ts[1:]) if w0 <= b < w1]
    out = {"tokens_per_s": tokens / (w1 - w0)}
    if ttft:
        out["ttft_p95_ms"] = float(np.percentile(ttft, 95) * 1e3)
    if gaps:
        out["itl_p50_ms"] = float(np.percentile(gaps, 50) * 1e3)
        out["itl_p95_ms"] = float(np.percentile(gaps, 95) * 1e3)
    return out


def device_info(devices, chips: int) -> Dict:
    d = devices[0]
    peak = 0
    for dev in devices[:chips]:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def warm_prompt_shapes(lengths, chunk: int, ps: int) -> int:
    """Compile the small eager operations the engine's chunked prefill
    runs once per prompt length and chunk length (slicing the prompt,
    scattering it into the padded chunk), for every prompt length the
    mix can draw.  They are a program property (see PERF.md); warming
    them keeps the compiler out of the window.  Returns how many shapes
    it ran."""
    import jax.numpy as jnp
    done = set()
    for p in lengths:
        if p <= chunk:
            pad = -(-p // ps) * ps
            jnp.zeros((1, pad), jnp.int32).at[0, :p].set(
                jnp.zeros((p,), jnp.int32))
            done.add(("whole", pad, p))
            continue
        prompt = jnp.zeros((p,), jnp.int32)
        for o in range(0, p, chunk):
            n = min(chunk, p - o)
            if ("tail", p, n) not in done:
                jnp.zeros((1, chunk), jnp.int32).at[0, :n].set(
                    prompt[o:o + n])
                done.add(("tail", p, n))
    return len(done)


_COMPILES: Dict = {}


def _compile_counter() -> Dict:
    """A process-wide count of lowerings and backend compiles while
    ``counting`` is set (the listener is registered once)."""
    if not _COMPILES:
        import jax
        _COMPILES.update(n=0, counting=False)

        def on_event(name, *_a, **_k):
            if _COMPILES["counting"] and name.endswith(
                    ("backend_compile_duration",
                     "jaxpr_to_mlir_module_duration")):
                _COMPILES["n"] += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)
    return _COMPILES


def free_program(engine) -> None:
    """Drop the program's pool and compiled state before the
    reference runs, so the reference sets no memory peak of its own."""
    import jax
    for x in jax.tree.leaves(engine._paged_caches):
        x.delete()
    engine._paged_caches = None
    gc.collect()


def main(argv=None, *, root: Path = spec.ROOT,
         bench_dir: Path = spec.BENCH_DIR, require_tpu: bool = True,
         compile_cache: bool = True, t_process: float = T_PROCESS,
         readings=check.readings) -> int:
    """One run of a cell.  ``readings`` computes the numbers compared
    from the sampled requests (``control.py`` puts the control there)."""
    args = parse(argv)
    stages = {"imports": time.perf_counter() - t_process}
    import jax
    devices = jax.devices()
    stages["devices"] = time.perf_counter() - t_process
    bench = spec.load(root)
    cell = spec.cell(bench, args.workload)
    if require_tpu and devices[0].platform != "tpu":
        print(f"chipbench: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return NO_CHIP
    if len(devices) < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return NO_CHIP
    src = str(Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    if compile_cache:
        # a fixed directory inside the checkout, whatever the
        # environment names: the path is part of the cache's key, and a
        # cache outside the checkout could be shared with another one
        cache_dir = str(Path(root).resolve() / ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        log(f"compile cache: {cache_dir}")

    from repro.serving.backend import InProcessBackend
    from repro.serving.engine import Engine, ServeConfig
    from repro.serving.scheduler import PagedLLMConfig, PagedLLMScheduler

    conf = spec.config(bench, cell["config"], root)
    mix = spec.traffic(cell["traffic"], bench_dir)
    ref = spec.reference(conf["family"], bench_dir)
    sv = conf["serving"]
    mcfg = model_config(conf)
    schedule = traffic_mod.Schedule(mix, args.seed, conf["vocab_size"])
    ps = sv["page_size"]
    max_len = -(-schedule.longest() // ps) * ps

    w = weights_mod.make(ref.weight_specs(conf), args.seed)
    params = ref.to_program(w, conf)
    from repro.models import transformer as tf
    want = tf.abstract_params(mcfg, sv["dtype"])
    if (jax.tree.structure(want) != jax.tree.structure(params)
            or [x.shape for x in jax.tree.leaves(want)]
            != [x.shape for x in jax.tree.leaves(params)]):
        raise ValueError("the reference's weights do not match the "
                         "program's parameter tree")
    jax.block_until_ready(params)
    stages["weights"] = time.perf_counter() - t_process
    engine = Engine(mcfg, params, ServeConfig(max_len=max_len))
    engine.init_paged(num_pages=sv["num_pages"], page_size=ps,
                      decode_batch=sv["decode_batch"])
    rec = Recorder(engine)
    backend = InProcessBackend(engine)
    sched = PagedLLMScheduler(backends=[backend], cfg=PagedLLMConfig(
        prefill_chunk_pages=sv["prefill_chunk_pages"],
        default_slo_ms=SLO_MS))
    submit = sched.submit

    def annotated_submit(*a, **kw):
        with jax.profiler.TraceAnnotation("cb_submit"):
            return submit(*a, **kw)
    sched.submit = annotated_submit

    stages["engine"] = time.perf_counter() - t_process
    chunk = sv["prefill_chunk_pages"] * ps
    lens = [n for n in schedule.prompt_lengths() if n <= chunk] or [ps]
    sched.warmup(lens)
    stages["scheduler warm-up"] = time.perf_counter() - t_process
    n_shapes = warm_prompt_shapes(schedule.prompt_lengths(), chunk, ps)
    stages[f"{n_shapes} prompt shapes"] = time.perf_counter() - t_process
    jax.block_until_ready(jax.tree.leaves(engine._paged_caches)[0])
    setup_s = time.perf_counter() - t_process
    log("set-up stages, seconds since start: " + ", ".join(
        f"{k} {v:.3f}" for k, v in stages.items()))
    log(f"set-up {setup_s:.3f} s: {conf['name']} {conf['num_layers']} "
        f"layers, pool {sv['num_pages']} x {ps}, decode batch "
        f"{sv['decode_batch']}, max_len {max_len}, warm prompt lengths "
        f"{sorted({-(-n // ps) * ps for n in lens})}")
    rec.decode.clear()
    rec.prefill.clear()

    compiles = _compile_counter()
    compiles["n"], compiles["counting"] = 0, False

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    traced = bool(args.trace)

    class Hooks:
        """Profiler on a second before the window; markers and the
        compile count at its edges."""

        def before(self):
            if traced:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(trace_dir, profiler_options=opts)

        def start(self):
            compiles["counting"] = True
            with jax.profiler.TraceAnnotation(trace.WINDOW_START):
                pass

        def end(self):
            with jax.profiler.TraceAnnotation(trace.WINDOW_END):
                pass
            compiles["counting"] = False

    run = asyncio.run(drive(sched, schedule, mix, args.seconds, Hooks()))
    if traced:
        jax.profiler.stop_trace()
    rows = request_rows(run)
    w0, w1 = run["w0"], run["w1"]
    in_window = [r for r in rows if w0 <= r["sent"] < w1]
    log(f"window {w1 - w0:.3f} s: {len(in_window)} requests sent, "
        f"{sum(r['failed'] for r in in_window)} failed, "
        f"{sum(r['first'] >= run['t_stop'] for r in in_window)} without a "
        f"first token; {sum(r['finished'] for r in rows)} of {len(rows)} "
        f"finished when the run stopped; compilations inside the window: "
        f"{compiles['n']}")
    device = device_info(devices, cell["chips"])

    metrics: Dict[str, Dict] = {}
    breakdown = None
    if traced:
        tr = trace.read(trace_dir)
        device["busy_s"] = trace.busy_s(tr)
        device["window_s"] = tr.window_s
        breakdown = trace.breakdown(tr)
        record = types.SimpleNamespace(
            config=conf, peaks=peaks.peaks(devices[0].device_kind),
            costs=costs, trace=tr, requests=rows, w0=w0, w1=w1,
            decode=[d for d in rec.decode if w0 <= d[0] < w1],
            prefill=[p for p in rec.prefill if w0 <= p[0] < w1])
        for m in spec.per_layer(bench, args.workload):
            value = spec.metric(m["name"], bench_dir).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = end_to_end(rows, w0, w1)
        e2e["setup_s"] = setup_s
        log("end to end: " + ", ".join(f"{k} {v!r}" for k, v in e2e.items()))
        for m in spec.end_to_end(bench, args.workload):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    shutil.rmtree(trace_dir, ignore_errors=True)

    free_program(engine)
    finished = [r for r in rows if r["finished"]]
    picked = check.sample(finished, args.seed, int(conf["check_requests"]))
    t_ref = time.perf_counter()
    values = readings(ref, w, conf, picked)
    log(f"reference over {len(picked)} requests "
        f"({sum(r['n_out'] for r in picked)} served tokens, longest "
        f"{max((r['prompt_len'] + r['n_out'] for r in picked), default=0)})"
        f": {time.perf_counter() - t_ref:.3f} s")
    limits = dict(conf["limits"])
    checks = {k: {"value": (v if math.isfinite(v) else None),
                  "limit": limits[k]} for k, v in values.items()}
    correct = bool(picked) and all(
        math.isfinite(v) and v <= limits[k] for k, v in values.items())
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    out = {"correct": correct, "attempted": len(in_window),
           "failed": int(sum(r["failed"] or r["first"] >= run["t_stop"]
                             for r in in_window)),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
