"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process does the whole run: it draws the weights on the device from the
seed, builds the serving engine, warms every program the cell's traffic uses,
runs the traffic's pre-roll, measures for ``--seconds`` through FrontDoor,
then frees the engine and checks a sample of the served tokens against the
plain reference that the configuration names (``cell.reference_module``).
With ``--trace 0`` the last line holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  Without a TPU, or with fewer chips than the
cell asks for, it exits 3 and prints no result.

``--control 1`` puts the control (the reference in bfloat16) in the
program's place for the comparison: its first choices at the positions of
the same sample are judged by the same limits, and ``correct`` is theirs.
The program's own readings are still printed on an earlier line.  The
benchmark's own runs leave it off.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

import cell  # noqa: E402
import check  # noqa: E402
import peaks  # noqa: E402
import traffic  # noqa: E402
import window as window_lib  # noqa: E402

OUT_DIR = os.path.join(ROOT, ".bench_out")
# the engine jits every step through one wrapper function, ``counted``: its
# programs' modules are ``jit_counted(<program id>)`` in a trace
ENGINE_MODULES = "jit_counted"


class NoChip(RuntimeError):
    pass


def _args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def device_info(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    d = devs[0]
    line = (f"device: platform={d.platform} kind={d.device_kind} "
            f"count={len(devs)}")
    if require_chip and d.platform != "tpu":
        raise NoChip(f"{line}: no TPU")
    if require_chip and len(devs) < chips:
        raise NoChip(f"{line}: the cell needs {chips} chips")
    print(line, flush=True)
    return ({"platform": d.platform, "kind": d.device_kind,
             "count": len(devs)}, devs[:chips])


def _annotate(engine):
    """Host spans around the engine calls the front door makes."""
    import jax
    for name in ("step", "drain", "reap"):
        fn = getattr(engine, name)

        def wrapped(*a, _fn=fn, _span=f"bench.engine.{name}", **k):
            with jax.profiler.TraceAnnotation(_span):
                return _fn(*a, **k)
        setattr(engine, name, wrapped)


def _reduce_trace(trace_dir, engine, n_chips):
    import trace_reduce as tr
    pd = tr.load(trace_dir)
    devs = tr.device_lines(pd)[:n_chips]
    if not devs:
        raise RuntimeError("the trace holds no TPU plane")
    spans = tr.host_spans(pd)
    lo, hi = tr.span(spans, "bench.window")
    order = (["decode"] * len(engine.decode_buckets)
             + ["prefill_chunk"] * len(engine.chunk_widths)
             + ["cow_copy", "scrub_block"])

    @functools.cache
    def programs():
        # mapped only for a reader that asks for one engine step's share;
        # a trace whose programs cannot be told apart fails the run
        return tr.program_map(devs[0]["modules"],
                              tr.span(spans, "bench.replay"), order,
                              ENGINE_MODULES)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": float(np.mean([tr.busy_ns(d["ops"], lo, hi)
                                 for d in devs])) * 1e-9,
        "kernel_seconds": lambda k, step: float(np.mean(
            [tr.kernel_seconds(d, k, programs(), step, lo, hi)
             for d in devs])),
        "breakdown": {"device_ops": tr.top_ops(devs[0], lo, hi),
                      "idle_gaps": tr.top_gaps(devs[0], spans, lo, hi)},
    }


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             control: bool = False, require_chip: bool = True,
             conf: dict = None, mix: dict = None, specs: dict = None):
    """One run; returns the result line as a dict.  Tests pass `conf`,
    `mix`, `specs` ({"end_to_end": [...], "per_layer": [...]} metric
    entries) and require_chip=False to drive a run on the CPU."""
    import jax
    from repro.launch.runtime import use_repo_dirs
    wl = cell.workload(name) if conf is None else {"chips": 1}
    conf = conf or cell.load_config(wl["config"])
    mix = mix or traffic.load_mix(wl["traffic"])
    specs = specs or _metric_specs(cell.benchmark(), name)
    if require_chip:
        # before the backend starts: libtpu's logs and the compile cache
        # stay inside the checkout
        cache_dir = use_repo_dirs()
        # every program in the cache after the first run, however fast it
        # compiled, so later runs' set-up does the same work
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    info, devices = device_info(wl["chips"], require_chip)
    if require_chip:
        print(f"compile cache: {cache_dir}", flush=True)
    # a run whose set-up missed the persistent cache compiled: the first run
    # of a cell in a checkout, whose setup_s stands apart from the others
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        for k in cache_events:
            if event == f"/jax/compilation_cache/cache_{k}":
                cache_events[k] += 1
    jax.monitoring.register_event_listener(on_event)

    import weights
    from repro.serve.engine import ServeEngine
    cfg = cell.model_config(conf)
    engine = ServeEngine(cfg, weights.make(cfg, seed),
                         cell.engine_config(conf))
    warm = engine.warmup()
    arrivals = traffic.schedule(mix, seed, seconds, cfg.vocab_size)

    trace_dir = None
    open_span = []
    if trace:
        # one trace per cell on disk: the next traced run replaces it
        trace_dir = os.path.join(OUT_DIR, "trace", name)
        if os.path.isdir(trace_dir):
            import shutil
            shutil.rmtree(trace_dir)
        _annotate(engine)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.replay"):
            engine.warmup()
            jax.block_until_ready(engine.caches)

    t_open = []

    def on_open():
        t_open.append(time.perf_counter())
        if trace:
            open_span.append(jax.profiler.TraceAnnotation("bench.window"))
            open_span[0].__enter__()

    def on_close():
        if trace:
            open_span[0].__exit__(None, None, None)

    w = window_lib.run(engine, arrivals, mix["preroll_s"], seconds, on_open,
                       on_close)
    setup_s = t_open[0] - T_START
    if trace:
        jax.profiler.stop_trace()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    info["memory_peak_bytes"] = int(peak)

    late = np.array([(c.sent - c.due) * 1e3 for c in w.clients])
    due = window_lib.due_in_window(w)
    unanswered = sum(1 for c in due if not c.times)
    errored = sum(1 for c in w.clients
                  if c.finish not in (None, "max_tokens", "eos"))
    jax.monitoring.unregister_event_listener(on_event)
    print(f"setup: warmup traced {warm} programs; persistent cache "
          f"hits={cache_events['hits']} misses={cache_events['misses']}; "
          f"setup_s={setup_s:.3f}; "
          f"requests sent={len(w.clients)} due_in_window={len(due)} "
          f"unanswered={unanswered} errored={errored}", flush=True)
    print(f"window: compiles_in_window={w.compiles_in_window} "
          f"generator_late_p99_ms={np.percentile(late, 99):.3f} "
          f"generator_late_max_ms={late.max():.3f} "
          f"first_tokens_in_window={sum(1 for c in due if c.times)}",
          flush=True)

    metrics = {}
    if trace:
        t = _reduce_trace(trace_dir, engine, len(devices))
        info["busy_s"] = t["busy_s"]
        info["window_s"] = t["window_s"]
        print(f"trace: busy_s={t['busy_s']!r} window_s={t['window_s']!r}",
              flush=True)
        events = engine.trace.events()
        ctx = types.SimpleNamespace(
            model=conf["model"], engine=conf["engine"],
            peak=peaks.peaks(info["kind"]),
            chips=len(devices), window=w, events=events,
            due_in_window=due, window_s=t["window_s"], busy_s=t["busy_s"],
            kernel_seconds=t["kernel_seconds"],
            decoded=[len(c.prompt) + k for c in w.clients
                     for k, ts in enumerate(c.times)
                     if w.start <= ts < w.end],
            chunks=[(ev["p0"], ev["tokens"]) for ev in events
                    if ev["event"] == "prefill_chunk"
                    and w.start <= ev["ts"] < w.end])
        for m in specs["per_layer"]:
            v = cell.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = window_lib.end_to_end(w)
        e2e["setup_s"] = setup_s
        for m in specs["end_to_end"]:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    finished = [(c.prompt, c.tokens) for c in w.clients
                if c.finish == "max_tokens"
                and len(c.tokens) == c.max_new_tokens]
    result = {"correct": False, "attempted": len(w.clients),
              "failed": unanswered + errored, "metrics": metrics,
              "device": info}
    if trace:
        result["breakdown"] = t["breakdown"]
    engine.close()
    del engine, w
    gc.collect()

    reference = cell.reference_module(conf)
    sample = check.pick(finished, seed)
    r = {}
    if sample:
        import jax.numpy as jnp
        ref = reference.Reference(conf, cfg, seed)
        ctl = (reference.Reference(conf, cfg, seed, jnp.bfloat16)
               if control else None)
        t0 = time.perf_counter()
        r, r_ctl = check.readings(ref, sample, ctl)
        print(f"check: {json.dumps(r)} reference_s="
              f"{time.perf_counter() - t0:.3f}", flush=True)
        if control:
            print(f"check (control in the program's place): "
                  f"{json.dumps(r_ctl)}", flush=True)
            r = r_ctl
    # no finished request to compare reads as null, and not correct
    result["checks"] = {k: {"value": r.get(k), "limit": v}
                        for k, v in conf["check"].items()}
    result["correct"] = all(c["value"] is not None and c["value"] <= c["limit"]
                            for c in result["checks"].values())
    return result


def _metric_specs(bench, name):
    """The metric entries of BENCHMARK.json that this cell reports."""
    return {kind: [m for m in bench[kind]
                   if "workloads" not in m or name in m["workloads"]]
            for kind in ("end_to_end", "per_layer")}


def main(argv=None) -> int:
    args = _args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), control=bool(args.control))
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
