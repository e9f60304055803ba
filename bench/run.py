#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (`BENCHMARK.json` workload) names a configuration and a traffic
mix; `bench/spec.py` finds their files. One run:

1. refuses a `REPRO_PALLAS*` kernel override, a device that is not a TPU,
   fewer chips than the cell asks for, and a `device_kind` without peaks;
2. builds the engine from the configuration's ``model_seed``
   (`bench/models/<builder>.py`), with JAX's compile cache in the
   checkout; the run's seed draws the traffic and the checked sample;
3. warms up the cell's own bucket shapes, and counts that as set-up;
4. drives the mix through `make_server` for `--seconds`, counting the
   programs lowered inside the window (there should be none);
5. with ``--trace 1`` profiles the window (or the mix's ``trace_seconds``
   of its middle) and reports the per-layer metrics and a breakdown,
   otherwise the end-to-end metrics;
6. compares a sample of what the window served, and what the same compiled
   stage programs give for it, with the plain reference
   (`bench/models/<builder>_ref.py`), each number against its limit.

Earlier lines go to standard error, the checks last; the last line of
standard output is the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import loadgen, spec as S  # noqa: E402
from bench.spec import BenchError  # noqa: E402

TRACE_DIR = ROOT / "bench_out" / "trace"
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


COUNTER = None  # the process's CompileCounter, made by the first setup


def log(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts the programs JAX lowers and compiles, process-wide."""

    def __init__(self):
        import jax

        self.lowered = self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == LOWERING_EVENT:
            self.lowered += 1
        elif name == COMPILE_EVENT:
            self.compiled += 1

    def read(self):
        return self.lowered, self.compiled


def _span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Profiler:
    """Profiles the host span ``bench.traced``, `lead` seconds into the
    window and `seconds` long, from a timer thread. Tracing starts before
    the window and stops `tail` seconds after the span, so that ops running
    at either edge are recorded whole (the profiler drops an op that is in
    flight when it starts or stops); the reduction clips them to the span.
    Python function tracing is off: only the runtime's and the benchmark's
    own spans are recorded.
    """

    def __init__(self, lead: float, seconds: float, tail: float):
        self.lead, self.seconds, self.tail = lead, seconds, tail
        self.thread = threading.Thread(target=self._run, name="bench-trace")
        self.started, self.go = threading.Event(), threading.Event()
        self.t0 = None
        self.error = None

    def _run(self):
        import jax

        try:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(str(TRACE_DIR),
                                     profiler_options=options)
            self.started.set()
            self.go.wait()
            start = self.t0 + self.lead
            time.sleep(max(start - time.perf_counter(), 0))
            with _span("bench.traced"):
                time.sleep(max(start + self.seconds - time.perf_counter(), 0))
            time.sleep(self.tail)
            jax.profiler.stop_trace()
        except Exception as e:  # reported by join(), the run then fails
            self.error = e
        finally:
            self.started.set()

    def begin(self):
        """Start tracing; returns once the profiler runs."""
        self.thread.start()
        self.started.wait()

    def window_starts(self, t0: float):
        self.t0 = t0
        self.go.set()

    def join(self):
        self.go.set()
        self.thread.join()
        if self.error is not None:
            raise BenchError(f"profiler failed: {self.error!r}")


@contextlib.contextmanager
def frozen_setup():
    """Set-up's objects (weights, catalog, compiled programs, the user
    pool) are moved out of the garbage collector's reach (`gc.freeze`), as
    a long-running server's would be after start-up; collection stays on
    in the window and walks what the window allocates."""
    gc.collect()
    gc.freeze()
    try:
        yield
    finally:
        gc.unfreeze()


def warm(build, server, engine, pool, buckets) -> None:
    """Run the stage programs once at every bucket shape of the mix."""
    for b in buckets:
        build.run_stages(server, engine, pool[:b], b)


def drive(traffic, server, pool, n_users, seed, seconds, span,
          on_start=None):
    """Drive the mix for `seconds`; -> (records, window, snapshots).

    The registry is read only while the server is idle: before the window
    and once every request is redeemed (a snapshot taken while the drain
    thread dispatches can read the donated cache-stats accumulator after
    it was deleted). Counter deltas so cover the window and the draining
    of the requests still open at its close.
    """
    import numpy as np

    rng = np.random.default_rng(loadgen.subseed(seed, 21))
    if traffic["loop"] == "open":  # drawn before the window opens
        offsets, users = loadgen.open_schedule(
            traffic["rate"], seconds, n_users, traffic["users_zipf"], rng)
    snap0 = server.snapshot()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    if on_start is not None:
        on_start(t0)
    if traffic["loop"] == "open":
        records, harvest = loadgen.open_loop(server, pool, users, offsets,
                                             t0, span)
        rest = ()
    elif traffic["loop"] == "closed":
        users = loadgen.user_cycle(n_users, traffic["users_zipf"], rng)
        records, harvest, rest = loadgen.closed_loop(
            server, pool, users.__next__, traffic["outstanding"], t_end,
            span)
    else:
        raise BenchError(f"unknown loop {traffic['loop']!r}")
    t1 = max(time.perf_counter(), t_end)
    loadgen.redeem(server, records, harvest, rest)
    return records, (t0, t1), (snap0, server.snapshot())


def sample_rows(records, n: int, seed: int):
    """Rows of `n` served and redeemed requests, drawn from the seed."""
    import numpy as np

    ok = np.flatnonzero((records.status == loadgen.OK) & records.redeemed)
    rng = np.random.default_rng(loadgen.subseed(seed, 23))
    return np.sort(rng.choice(ok, min(n, len(ok)), replace=False))


def redrive(build, server, engine, queries, max_batch: int) -> dict:
    """What the window's compiled stage programs give for `queries`, in
    buckets of the full batch shape."""
    import numpy as np

    parts = [build.run_stages(server, engine, queries[i:i + max_batch],
                              max_batch)
             for i in range(0, len(queries), max_batch)]
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def setup(workload: str, seed: int, *, root: pathlib.Path = ROOT,
          require_chip: bool = True, device_kind: str | None = None,
          t_start: float = T_START):
    """Everything before the window: the cell's files, the device checks,
    the engine, the server, the warm-up.

    `require_chip=False` (tests) skips the look for a TPU, and
    `device_kind` then names the peaks to use.
    """
    spec = S.load_spec(root)
    cell = S.cell(spec, workload, root)
    cfg, traffic, w = cell["config"], cell["traffic"], cell["workload"]
    overrides = sorted(k for k in os.environ if k.startswith("REPRO_PALLAS"))
    if overrides:
        raise BenchError(f"kernel overrides set: {overrides}")
    src = root / "src"
    if not (src / "repro").is_dir():
        raise BenchError(f"no program under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    # the TPU runtime logs to a fixed path outside the checkout otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.launch.runtime import enable_compile_cache

    log("compile cache", enable_compile_cache())
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_default_matmul_precision", cfg["precision"])
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise BenchError(f"JAX's first device is {devices[0].platform} "
                             f"({devices[0].device_kind}), not a TPU")
        if len(devices) < w["chips"]:
            raise BenchError(f"the cell needs {w['chips']} chips, JAX sees "
                             f"{len(devices)}")
    peaks = S.peaks(devices[0].device_kind if require_chip else device_kind,
                    root)
    log(f"device {devices[0].platform} kind={devices[0].device_kind} "
        f"count={len(devices)}; workload {workload} seed {seed}")
    global COUNTER
    if COUNTER is None:
        COUNTER = CompileCounter()
    build, ref_mod = S.builder(cfg)

    from repro.serving import make_server

    built = build.build(cfg, log)
    knobs = dict(traffic["server"])
    mode = knobs.pop("mode")
    server = make_server(built["engine"], mode, **knobs)
    t = time.perf_counter()
    buckets = traffic["warm_buckets"]
    if buckets == "pow2":
        from repro.serving.batcher import default_buckets

        buckets = default_buckets(knobs["max_batch"])
    warm(build, server, built["engine"], built["pool"], buckets)
    built["setup_parts"]["warm_s"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    parts = {k: round(v, 3) for k, v in built["setup_parts"].items()}
    log(f"setup_s={setup_s:.3f} parts {json.dumps(parts)}")
    return types.SimpleNamespace(
        spec=spec, cfg=cfg, traffic=traffic, workload=w, build=build,
        ref_mod=ref_mod, built=built, server=server, max_batch=knobs[
            "max_batch"], devices=devices, peaks=peaks, setup_s=setup_s,
        root=root)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        control: bool = False, t_start: float = T_START, **where) -> dict:
    """One run of `workload`; -> the result dict (see module docstring).

    `where` goes to `setup`. `control=True` (bench/calibrate.py) also
    reads the compared numbers of the control: the reference computed at
    the precision below the configuration's, in the program's place.
    """
    import numpy as np

    st = setup(workload, seed, t_start=t_start, **where)
    server, engine = st.server, st.built["engine"]
    traffic, build, ref_mod = st.traffic, st.build, st.ref_mod
    span = _span if trace else loadgen._no_span
    profiler = None
    if trace:
        traced = min(traffic.get("trace_seconds", seconds), seconds)
        profiler = Profiler((seconds - traced) / 2, traced,
                            traffic.get("trace_tail_seconds", 0.5))
        profiler.begin()
    c0 = COUNTER.read()
    with frozen_setup():
        records, window, (snap0, snap1) = drive(
            traffic, server, st.built["pool"], st.built["n_users"], seed,
            seconds, span, profiler.window_starts if profiler else None)
    c1 = COUNTER.read()
    if profiler is not None:
        profiler.join()
    log(f"compiles in window: {c1[0] - c0[0]} programs lowered, "
        f"{c1[1] - c0[1]} compiled")
    in_window = records.take(records.due <= window[0] + seconds)
    failed = int(np.sum(in_window.status != loadgen.OK))

    sample = sample_rows(in_window, traffic["sample"], seed)
    if not len(sample):
        raise BenchError("no request was served in the window")
    queries = [st.built["pool"][u] for u in in_window.user[sample].tolist()]
    prog = redrive(build, server, engine, queries, st.max_batch)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in st.devices[:st.workload["chips"]])
    reduced = None
    if trace:
        from bench import trace_reduce as TR

        t = time.perf_counter()
        pd = TR.load(TRACE_DIR)
        try:
            reduced = TR.reduce(pd, TR.span_window(pd, "bench.traced"),
                                programs=build.TRACE_PROGRAMS,
                                kernels=build.TRACE_KERNELS)
        except ValueError as e:
            raise BenchError(f"trace: {e}") from e
        del pd
        log(f"trace reduced in {time.perf_counter() - t:.1f}s: "
            f"{json.dumps(reduced)}")
    ctx = types.SimpleNamespace(
        records=in_window, window=window, seconds=seconds, snap0=snap0,
        snap1=snap1, trace=reduced, setup_s=st.setup_s, peaks=st.peaks,
        work=build.work(st.cfg, engine),
        count=lambda n: S.count(n, st.root), values={})
    server.close()
    del server, engine, st.server, st.built["engine"]
    gc.collect()

    t = time.perf_counter()
    reference = ref_mod.Reference(st.built["reference_inputs"])
    batch = {k: np.stack([np.asarray(q[k]) for q in queries])
             for k in queries[0]}
    want = reference.serve(batch)
    items, scores = in_window.items[sample], in_window.scores[sample]
    numbers = ref_mod.numbers(prog, items, scores, want,
                              reference.rescore(want, items))
    numbers["failed"] = float(failed)
    numbers["trace_dropped"] = float(snap1.get("serving.trace_dropped", 0))
    log(f"reference over {len(sample)} requests in "
        f"{time.perf_counter() - t:.1f}s")
    limits = dict(st.cfg["checks"], failed=0, trace_dropped=0)
    for name, value in numbers.items():
        if name not in limits:
            log(f"unchecked {name} = {value!r}")

    metrics = {}
    for kind in ["end_to_end"] + (["per_layer"] if trace else []):
        for entry, reader in S.metrics(st.spec, workload, kind, st.root):
            value = reader.read(ctx)
            ctx.values[entry["name"]] = value
            if value is not None and (kind == "per_layer") == trace:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
    first = st.devices[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(st.devices), "memory_peak_bytes": int(peak)}
    result = {"correct": all(numbers[k] <= v for k, v in limits.items()),
              "attempted": len(in_window), "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if control:
        low = ref_mod.Reference(st.built["reference_inputs"], "high")
        got = low.serve(batch)
        result["numbers"] = numbers
        result["control"] = ref_mod.numbers(
            got, got["items"], got["scores"], want,
            reference.rescore(want, got["items"]))
    result["checks"] = {k: {"value": numbers[k], "limit": v}
                        for k, v in limits.items()}
    for k, v in limits.items():
        print(f"bench: check {k} = {numbers[k]!r} (limit {v!r})",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        print(f"bench: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
