"""Run one cell of the benchmark on the machine this is started on.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip: it builds the engine exactly as
``engine.server.main()`` does (``Scheduler`` + ``create_engine_app``;
only the preset lookup is bypassed: the configuration comes from
``configs/<name>.json`` through its architecture module, ``arch/``),
serves the app on 127.0.0.1, warms up every
program shape the traffic mix uses, then lets ``loadgen.py`` — a child
that never imports JAX — drive the HTTP front for ``--seconds``.  The
last line of standard output is the result; everything else goes on
earlier lines, to stderr or under ``benchmarks/out/``.

Without a TPU the command fails, except under ``--rehearse``, which takes
the configuration's tiny sizes on the CPU, prints the device as CPU and
is never a measurement.
"""

from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse
import asyncio
import gc
import json
import logging
import os
import shutil
import socket
import subprocess
import sys
import threading
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(REPO))

import metrics_lib  # noqa: E402
from metrics_lib import load_reader  # noqa: E402
import reduce_trace  # noqa: E402
import traffic  # noqa: E402
from tokenizer import BenchTokenizer, piece_ids  # noqa: E402

# Long enough that the requests and module executions cut by its two
# edges are few beside those inside (prefills last 1-1.5 s, a tick 0.2 s).
TRACE_SECONDS = 10.0
# The control of the reference check: the program's own path in the nearest
# precision below the one the configurations state (bf16 activations ->
# int8, per token, in every projection), switched on by ``--control``.
CONTROL_ENGINE = {"matmul_kernel": "pallas_w8a8"}


def log(msg: str, **fields) -> None:
    """An earlier line of output: JSON, so that a log can be grepped."""
    print(json.dumps({"bench": msg, **fields}, default=str), flush=True)


def fail(msg: str, code: int = 3):
    print(f"benchmarks/run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


# -- data -------------------------------------------------------------------


def load_cell(name: str) -> dict:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        fail(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}", 2)
    cell = cells[name]
    config = json.loads((HERE / "configs" / f"{cell['config']}.json").read_text())
    mix = traffic.load_mix(cell["traffic"])
    arrivals = dict(mix["arrivals"])
    cell_file = HERE / "cells" / f"{name}.json"
    if cell_file.exists():
        arrivals.update(json.loads(cell_file.read_text()).get("arrivals", {}))

    def wanted(metric: dict) -> bool:
        return "workloads" not in metric or name in metric["workloads"]

    return {
        "cell": cell,
        "config": config,
        "mix": mix,
        "arrivals": arrivals,
        "end_to_end": [m for m in bench["end_to_end"] if wanted(m)],
        "per_layer": [m for m in bench["per_layer"] if wanted(m)],
    }


ARCH_EXPORTS = ("llama_config", "last_logits", "decode_step_bytes", "prefill_flops")


def load_arch(config: dict):
    """``arch/<name>.py``, named by the configuration's ``"arch"`` key
    (absent: ``llama``): the mapping to the program's model
    configuration, the plain reference and the roofline counts."""
    path = HERE / "arch" / f"{config.get('arch', 'llama')}.py"
    if not path.exists():
        fail(f"{path.relative_to(REPO)} does not exist", 2)
    module = metrics_lib.load_module(path)
    missing = [f for f in ARCH_EXPORTS if not callable(getattr(module, f, None))]
    if missing:
        fail(f"{path.relative_to(REPO)} lacks {missing}", 2)
    return module


# -- the server -------------------------------------------------------------


class Server:
    """The engine's aiohttp app on its own thread and loop."""

    def __init__(self, app) -> None:
        self.app = app
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.sock.bind(("127.0.0.1", 0))
        self.port = self.sock.getsockname()[1]
        self.loop = asyncio.new_event_loop()
        self.ready = threading.Event()
        self.thread = threading.Thread(target=self._serve, daemon=True)

    def _serve(self) -> None:
        from aiohttp import web

        asyncio.set_event_loop(self.loop)
        runner = web.AppRunner(self.app, access_log=None)
        self.loop.run_until_complete(runner.setup())
        self.loop.run_until_complete(web.SockSite(runner, self.sock).start())
        self.ready.set()
        self.loop.run_forever()
        self.loop.run_until_complete(runner.cleanup())
        self.loop.close()

    def start(self) -> None:
        self.thread.start()
        if not self.ready.wait(30):
            fail("the HTTP front did not come up")

    def stop(self) -> None:
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(30)


class CompileNames(logging.Handler):
    """What compiles inside the window, by name (``jax_log_compiles``
    is switched on when the window starts): none should."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.names: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling ") and len(self.names) < 20:
            self.names.append(msg[:160])


class TickFailures(logging.Handler):
    """Counts the scheduler's 'tick failed' records: a run with one is
    not correct, whatever its requests returned."""

    def __init__(self) -> None:
        super().__init__(level=logging.ERROR)
        self.count = 0

    def emit(self, record: logging.LogRecord) -> None:
        if "tick failed" in record.getMessage():
            self.count += 1


class GcPauses:
    """Python's garbage collections inside the window, by generation:
    the server shares this process, and a collection holds the tick
    thread and the front alike.  Logged, never part of a metric."""

    def __init__(self) -> None:
        self.pauses: list[tuple[int, float]] = []
        self._since = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._since = time.perf_counter()
        else:
            self.pauses.append((info["generation"], time.perf_counter() - self._since))

    def summary(self) -> dict:
        by_gen = {g: [s for gen, s in self.pauses if gen == g] for g in (0, 1, 2)}
        return {
            f"gen{g}": {"count": len(v), "total_s": sum(v), "longest_s": max(v, default=0.0)}
            for g, v in by_gen.items()
        }


def watch_compiles() -> dict:
    """Counts every request for an executable, cached or not, under
    ``"requests"``: none may come inside the window."""
    from jax import monitoring

    seen = {"requests": 0}

    def on_event(name: str, **_) -> None:
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            seen["requests"] += 1

    monitoring.register_event_listener(on_event)
    return seen


def run_burst(scheduler, burst: list, timeout: float) -> list:
    """Submit a burst while the tick loop is stopped, so that it is
    admitted as one batch; returns the finish reasons."""
    from generativeaiexamples_tpu.engine.sampler import SamplingParams
    from generativeaiexamples_tpu.engine.scheduler import Request

    scheduler.stop()
    done = [threading.Event() for _ in burst]
    reasons: list = [None] * len(burst)

    def on_done(i):
        def fn(reason: str) -> None:
            reasons[i] = reason
            done[i].set()

        return fn

    for i, r in enumerate(burst):
        ok = scheduler.submit(
            Request(
                token_ids=list(r["prompt"]),
                sampling=SamplingParams(
                    temperature=r["temperature"], top_p=r["top_p"],
                    max_tokens=r["max_tokens"],
                ),
                on_token=lambda _tid: None,
                on_done=on_done(i),
                eos_id=None,
                id=f"warm-{i}",
            )
        )
        if not ok:
            fail("a warm-up request was refused")
    scheduler.start()
    deadline = time.monotonic() + timeout
    for ev in done:
        if not ev.wait(max(0.0, deadline - time.monotonic())):
            fail("a warm-up burst did not finish")
    return reasons


def http_json(port: int, path: str, body: dict, timeout: float = 600.0) -> dict:
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def reference_check(arch, scheduler, cfg, port, prompts, ref_cfg, pad_to) -> dict:
    """The server's first greedy token against the float32 reference of
    the configuration's architecture module.

    A prompt agrees when the served token's reference logit lies within
    ``tolerance`` x max |logit| of the reference maximum; at least
    ``min_within`` of the prompts must agree (the configuration's file
    says why not all)."""
    import numpy as np

    rows = []
    started = time.monotonic()
    for prompt in prompts:
        out = http_json(
            port, "/v1/completions",
            {"prompt": prompt, "max_tokens": 1, "temperature": 0.0, "stream": False},
        )
        served = piece_ids(out["choices"][0]["text"])
        if len(served) != 1:
            return {"ok": False, "why": f"served {len(served)} tokens for max_tokens 1"}
        logits = np.asarray(arch.last_logits(scheduler.params, cfg, prompt, pad_to))
        rows.append(
            {
                "gap": float(logits.max() - logits[served[0]]) / float(np.abs(logits).max()),
                "rank": int((logits > logits[served[0]]).sum()),
            }
        )
    within = sum(1 for r in rows if r["gap"] <= float(ref_cfg["tolerance"]))
    return {
        "ok": within >= int(ref_cfg["min_within"]),
        "within": within,
        "of": len(rows),
        "worst_gap_share": max(r["gap"] for r in rows),
        "tolerance": ref_cfg["tolerance"],
        "argmax_agree": sum(1 for r in rows if r["rank"] == 0),
        "seconds": time.monotonic() - started,
        "prompts": rows,
    }


def longest_ticks(scheduler, t0: float, window_s: float, n: int = 3) -> list:
    """The window's ``n`` longest busy ticks, seconds by phase, from the
    scheduler's own tick record (what ``GET /debug/ticks`` serves; its
    ``t_start`` is on the clock ``t0`` is on).  A stall is read from
    these: which phase held the tick thread, and for how long."""
    phases = ("plan_s", "dispatch_s", "wait_device_s", "emit_s", "telemetry_s")
    t0 += time.perf_counter() - time.monotonic()  # the record's clock
    ticks = [
        r for r in scheduler.tick_records(4096)  # its whole ring
        if t0 <= r["t_start"] < t0 + window_s
    ]
    ticks.sort(key=lambda r: sum(r[p] for p in phases))
    return [
        {"at_s": r["t_start"] - t0, "total_s": sum(r[p] for p in phases),
         **{k: r[k] for k in phases + ("prefill_chunks", "admitted", "decode_lanes", "kv_bucket")}}
        for r in reversed(ticks[-n:])
    ]


def counters(scheduler) -> dict:
    s = scheduler.stats.snapshot()
    s["ttft_sum_ms"] = s["ttft_avg_ms"] * s["ttft_count"]
    return s


def delta(after: dict, before: dict) -> dict:
    return {
        k: after[k] - before[k]
        for k in after
        if isinstance(after[k], (int, float)) and k in before
    }


# -- one run ------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=[0, 1], default=0)
    parser.add_argument(
        "--rehearse", action="store_true",
        help="tiny sizes on the CPU, to rehearse the harness; never a measurement",
    )
    parser.add_argument(
        "--rate", type=float, default=0.0,
        help="override the open loop's rate (the sweep uses this; a cell's "
        "runs do not)",
    )
    parser.add_argument(
        "--control", action="store_true",
        help="serve through the control path (below); the reference check has "
        "to fail it; never a measurement",
    )
    args = parser.parse_args(argv)
    spec = load_cell(args.workload)
    model = dict(spec["config"])
    engine = dict(model["engine"])
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        model.update(model["rehearse"]["model"])
        engine.update(model["rehearse"]["engine"])
    if args.control:
        engine.update(CONTROL_ENGINE)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        fail(f"no TPU found (JAX's backend is {platform!r}); --rehearse runs tiny sizes on the CPU")
    if args.rehearse and platform != "cpu":
        fail("--rehearse is for the CPU")
    if len(devices) < int(spec["cell"]["chips"]):
        fail(f"the cell asks for {spec['cell']['chips']} chips, JAX reports {len(devices)}")
    from generativeaiexamples_tpu.utils.jax_runtime import device_report

    device = device_report()
    peaks_table = json.loads((HERE / "peaks.json").read_text())
    if device["kind"] not in peaks_table and not args.rehearse:
        fail(f"device kind {device['kind']!r} is not in peaks.json")
    peaks = peaks_table.get(device["kind"])

    from generativeaiexamples_tpu.engine.scheduler import Scheduler
    from generativeaiexamples_tpu.engine.server import create_engine_app
    from generativeaiexamples_tpu.utils.jax_runtime import (
        enable_compile_cache,
        runtime_report,
    )

    cache_dir = enable_compile_cache()
    # Small programs too: a second run should find every program cached.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = watch_compiles()
    ticks = TickFailures()
    # Cutting the streams at the window's end makes the front log one
    # "cannot write to closing transport" per request; a real handler
    # error reaches the client and counts as failed there.
    logging.getLogger("aiohttp.server").setLevel(logging.CRITICAL)
    logging.getLogger("generativeaiexamples_tpu").addHandler(ticks)
    out_dir = HERE / "out" / args.workload
    out_dir.mkdir(parents=True, exist_ok=True)

    arch = load_arch(model)
    cfg = arch.llama_config(model, engine)
    weight_seed = (args.seed ^ (args.seed >> 31)) & 0x7FFFFFFF
    t = time.monotonic()
    scheduler = Scheduler(
        cfg,
        None,
        max_batch=int(engine["max_batch"]),
        max_len=int(engine["max_len"]),
        decode_chunk_size=int(engine["decode_chunk_size"]),
        seed=weight_seed,
        prefill_chunk_tokens=int(engine["prefill_chunk_tokens"]) or None,
        prefix_cache=str(engine["prefix_cache"]),
        quantize=engine["weight_dtype"] == "int8",
        matmul_kernel=str(engine["matmul_kernel"]),
        kv_layout=str(engine["kv_layout"]),
    )
    log("scheduler built", seconds=time.monotonic() - t, cache_dir=cache_dir,
        matmul_kernel=scheduler.matmul_kernel)
    app = create_engine_app(
        scheduler, BenchTokenizer(cfg.vocab_size), None, None,
        model_name=spec["cell"]["config"], enable_profiler=False,
    )
    server = Server(app)
    server.start()

    vocab = cfg.vocab_size
    mix = spec["mix"]
    arrivals = spec["arrivals"]
    t = time.monotonic()
    for burst in traffic.warmup_bursts(mix, args.seed, vocab):
        reasons = run_burst(scheduler, burst, timeout=1100.0)
        if any(r != "length" for r in reasons):
            fail(f"a warm-up request ended {reasons}")
    # One request through the HTTP front, so that the window's first is not
    # the front's first; of the reference check's shape, which the plan warmed.
    http_json(server.port, "/v1/completions",
              {"prompt": [256 + i % 200 for i in range(int(mix["reference_len"][1]) - 4)],
               "max_tokens": 1, "temperature": 0.0})
    log("warmed up", seconds=time.monotonic() - t, compile=runtime_report()["compile"],
        compile_requests=compiles["requests"],
        memory=jax.local_devices()[0].memory_stats())

    rate = float(args.rate or arrivals.get("rate_rps", 0.0))
    if arrivals["loop"] == "open" and rate <= 0:
        fail("an open loop needs arrivals.rate_rps in the cell's file")
    n_requests = int(float(mix["supply_rps"]) * args.seconds) + int(arrivals.get("clients", 0)) + 8
    requests = traffic.generate(
        mix, args.seed, vocab, n_requests, rate if arrivals["loop"] == "open" else 0.0
    )
    plan = {
        "url": f"http://127.0.0.1:{server.port}/v1/completions",
        "loop": arrivals["loop"],
        "clients": int(arrivals.get("clients", 0)),
        "window_s": float(args.seconds),
        "requests": requests,
    }
    plan_path, records_path = out_dir / "plan.json", out_dir / "records.json"
    plan_path.write_text(json.dumps(plan))
    records_path.unlink(missing_ok=True)

    compiles_before = compiles["requests"]
    compiled = CompileNames()
    logging.getLogger("jax").addHandler(compiled)
    jax.config.update("jax_log_compiles", True)
    child_env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    gc_pauses = GcPauses()
    gc.callbacks.append(gc_pauses)
    child = subprocess.Popen(
        [sys.executable, str(HERE / "loadgen.py"), str(plan_path), str(records_path)],
        stdout=subprocess.PIPE, text=True, env=child_env,
    )
    try:
        first = child.stdout.readline()
        if not first.startswith("T0 "):
            fail(f"the load generator said {first!r}")
        t0 = float(first.split()[1])
        setup_s = t0 - PROCESS_START
        before = counters(scheduler)
        trace_window = trace_counters = None
        trace_dir = out_dir / "trace"
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            start_at = t0 + max(0.0, (args.seconds - TRACE_SECONDS) / 2.0)
            time.sleep(max(0.0, start_at - time.monotonic()))
            jax.profiler.start_trace(str(trace_dir))
            with jax.profiler.TraceAnnotation(reduce_trace.MARK_START):
                a = time.monotonic() - t0
                at_start = counters(scheduler)
            time.sleep(min(TRACE_SECONDS, args.seconds))
            with jax.profiler.TraceAnnotation(reduce_trace.MARK_END):
                b = time.monotonic() - t0
                trace_counters = delta(counters(scheduler), at_start)
            jax.profiler.stop_trace()
            trace_window = (a, b)
            log("traced", window=trace_window, stop_took=time.monotonic() - t0 - b)
        rest = child.stdout.read()
        rc = child.wait(timeout=args.seconds + 300)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    after = counters(scheduler)
    gc.callbacks.remove(gc_pauses)
    slow_ticks = longest_ticks(scheduler, t0, float(args.seconds))
    compiles_in_window = compiles["requests"] - compiles_before
    jax.config.update("jax_log_compiles", False)
    if rc != 0:
        fail(f"the load generator exited {rc}: {rest[-2000:]}")
    for line in rest.splitlines():
        print(line, flush=True)
    loaded = json.loads(records_path.read_text())
    records, gen = loaded["records"], loaded["summary"]

    # -- correct? -------------------------------------------------------------
    report = runtime_report()
    complete = [r for r in records if r["finish"] not in (None, "cut")]
    failed = [
        r for r in records
        if (r["finish"] in (None, "cut") and not r["tokens"])  # no first token in time
        or (
            r["finish"] not in (None, "cut")
            and (r["finish"] != "length" or len(r["tokens"]) != r["max_tokens"])
        )
    ]
    want_paths = {} if args.rehearse else model.get("expect_paths", {})
    paths_ok = all(
        any(site.startswith(prefix) for site in report["kernel_paths"])
        and all(
            taken == want
            for site, taken in report["kernel_paths"].items()
            if site.startswith(prefix)
        )
        for prefix, want in want_paths.items()
    )
    try:
        ref = reference_check(
            arch, scheduler, cfg, server.port,
            traffic.reference_prompts(mix, args.seed, vocab, int(model["reference"]["prompts"])),
            model["reference"], int(mix["reference_len"][1]),
        )
    except Exception as exc:  # the run still reports, as not correct
        ref = {"ok": False, "why": f"{type(exc).__name__}: {str(exc)[:400]}"}
    checks = {
        "outputs_full_length": not failed and bool(complete),
        "no_tick_failure": ticks.count == 0,
        "no_compile_in_window": compiles_in_window == 0,
        "kernel_paths": paths_ok,
        "reference": ref["ok"],
        "supply_lasted": not gen["supply_exhausted"],
    }
    correct = all(checks.values())
    log("checks", checks=checks, arch=Path(arch.__file__).stem,
        compiles_in_window=compiles_in_window,
        compiled_in_window=compiled.names,
        reference_check=ref, kernel_paths=report["kernel_paths"],
        finishes=sorted({str(r["finish"]) for r in records}))

    # -- metrics --------------------------------------------------------------
    window_s = float(args.seconds)
    is_open = arrivals["loop"] == "open"
    ttft = metrics_lib.ttfts_ms(records, from_due=is_open)
    gaps = metrics_lib.token_gaps_ms(records)
    e2e_values = {
        "setup_s": setup_s,
        "ttft_p50_ms": metrics_lib.percentile(ttft, 50) if ttft else None,
        "ttft_p90_ms": metrics_lib.percentile(ttft, 90) if ttft else None,
        "itl_p95_ms": metrics_lib.percentile(gaps, 95) if gaps else None,
        "out_tok_s": metrics_lib.tokens_in_window(records, window_s) / window_s,
    }
    units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
    summary = None
    if args.trace:
        import re

        xplane = reduce_trace.find_xplane(trace_dir)
        if args.rehearse:
            read = reduce_trace.read_xplane(
                xplane, re.compile(r"^/host:CPU$"), every_line_is_ops=True
            )
        else:
            read = reduce_trace.read_xplane(xplane)
        summary = reduce_trace.summarize(read)
        (out_dir / "trace_summary.json").write_text(
            json.dumps({**summary, "planes": read["planes"]}, indent=1)
        )
        (out_dir / "trace_sample.json").write_text(
            json.dumps(reduce_trace.sample(read))
        )
        shutil.rmtree(trace_dir, ignore_errors=True)  # tens of MB a run
        log("trace", window_s=summary["window_s"], window_from=summary["window_from"],
            busy_s=summary["busy_s"], modules=summary["modules"])
    ctx = {
        "trace": summary,
        "trace_window": trace_window,
        "counters": delta(after, before),
        "trace_counters": trace_counters,
        "records": records,
        "model": model,
        "engine": engine,
        "arch": arch,
        "peaks": peaks,
        "window_s": window_s,
    }
    metrics = {}
    if args.trace:
        for m in spec["per_layer"]:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
    else:
        for m in spec["end_to_end"]:
            value = e2e_values.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
    def in_flight(at: float) -> int:
        return sum(
            1 for r in records
            if r["sent"] is not None and r["sent"] <= at and (r["end"] is None or r["end"] > at)
        )

    def ttft_mean(lo: float, hi: float):
        xs = [
            (r["tokens"][0] - r["sent"]) * 1000.0 for r in records
            if r["tokens"] and lo <= r["sent"] < hi
        ]
        return metrics_lib.mean(xs) if xs else None

    log("window", requests_sent=len(records),
        ttft_mean_mid_third_ms=ttft_mean(window_s / 3, 2 * window_s / 3),
        ttft_mean_last_third_ms=ttft_mean(2 * window_s / 3, window_s),
        in_flight_mid=in_flight(window_s / 2), in_flight_end=in_flight(window_s), complete=len(complete),
        ttft_samples=len(ttft), gap_samples=len(gaps), end_to_end=e2e_values,
        counters=ctx["counters"], loadgen=gen, rate_rps=rate if is_open else None,
        t0_wall=time.time() - (time.monotonic() - t0),
        backlog_end=after["queued"], active_end=after["active_slots"], longest_ticks=slow_ticks,
        gc_in_window=gc_pauses.summary(),
        memory=jax.local_devices()[0].memory_stats())

    device["memory_peak_bytes"] = report["peak_bytes_in_use"]
    result = {
        "correct": bool(correct),
        "attempted": len(records),
        "failed": len(failed),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {
            "device_ops": summary["device_ops"],
            "idle_gaps": summary["idle_gaps"],
        }
    if args.rehearse:
        result["rehearsal"] = "tiny sizes on the CPU: not a measurement"
    if args.control:
        result["control"] = "served through the control path: not a measurement"

    server.stop()
    scheduler.stop()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
