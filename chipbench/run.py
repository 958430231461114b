"""One process, one cell, once:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Claims the cell's chips (refuses any platform but ``tpu``), sets up, warms up
the cell's own shapes, measures for ``--seconds`` and prints one JSON object
as the last line of stdout.  Everything about a cell is data found by name:
``BENCHMARK.json``, ``configs/``, ``workloads/``, ``layer_metrics/``.
"""
import time

T0 = time.perf_counter()      # set-up counts from process start

import argparse                                            # noqa: E402
import importlib                                           # noqa: E402
import json                                                # noqa: E402
import os                                                  # noqa: E402
import sys                                                 # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import manifest                             # noqa: E402


class Context:
    """What a kind's ``run(ctx)`` reads and fills."""

    def __init__(self, cell, devices, seed, seconds, trace, t0):
        self.cell, self.cfg, self.wl = cell, cell["cfg"], cell["wl"]
        self.devices, self.seed, self.seconds = devices, seed, float(seconds)
        self.trace, self.t0 = bool(trace), t0
        self.trace_dir = os.path.join(cell["root"], "chipbench", ".out",
                                      cell["name"], "trace")
        self.checks, self.e2e, self.counters, self.series = [], {}, {}, {}
        self.compared = {}      # each number compared, beside its limit
        self.attempted = self.failed = self.executables = 0
        self.reduced = self.peaks = self._capture = None

    def log(self, msg):
        print(f"[chipbench] {msg}", flush=True)

    def check(self, name, ok, detail="", key=None, value=None, limit=None):
        """One comparison that ``correct`` rests on.  ``key`` is a short
        plain name under which the number compared and its limit go onto the
        result's line and, as the run's last lines, to standard error."""
        self.checks.append(bool(ok))
        if key is not None:
            self.compared[key] = {"value": value, "limit": limit,
                                  "ok": bool(ok)}
        self.log(f"[{'ok' if ok else 'FAIL'}] {name}"
                 + (f": {detail}" if detail else ""))

    def watch_compiles(self):
        """Count in ``executables``, from here on, every executable jax
        compiles or loads from its cache in this process, whoever asked."""
        import jax

        def on_event(event, _seconds, **_):
            self.executables += event.endswith("/backend_compile_duration")
        jax.monitoring.register_event_duration_secs_listener(on_event)

    def poll_trace(self, now):
        """Call through the window: a traced run profiles its last
        ``trace_s`` seconds; an untraced one does nothing."""
        if self.trace and self._capture is None \
                and now >= self.seconds - self.wl["trace_s"]:
            from chipbench.trace import reduce
            self._capture = reduce.capture(self.trace_dir)
            self._capture.__enter__()

    def close_trace(self):
        capture, self._capture = self._capture, False     # never reopened
        if capture:
            capture.__exit__(None, None, None)

    def open_window(self):
        """Call at the start of the measured window: set-up ends here."""
        now = time.perf_counter()
        self.e2e["setup_s"] = now - self.t0
        return now


def run_cell(cell, devices, seed, seconds, trace, t0=None):
    """Run one cell on ``devices`` and return the result object."""
    import jax
    from mxnet_tpu import telemetry
    from mxnet_tpu.config import setup_compile_cache
    from chipbench import flops, readers

    # the served and benchmarked precision: one bf16 pass on the MXU
    jax.config.update("jax_default_matmul_precision", "bfloat16")
    setup_compile_cache()
    ctx = Context(cell, devices, seed, seconds, trace,
                  time.perf_counter() if t0 is None else t0)
    ctx.watch_compiles()
    dev = devices[0]
    ctx.check("runs on a TPU", dev.platform == "tpu",
              f"{len(devices)} x {dev.device_kind} ({dev.platform})",
              key="tpu_chips", value=len(devices) * (dev.platform == "tpu"),
              limit=f">= {cell['chips']}")
    try:
        importlib.import_module(f"chipbench.kinds.{ctx.cfg['kind']}").run(ctx)
    finally:
        ctx.close_trace()       # a kind that raised mid-window left it open
        telemetry.disable()
    stats = [d.memory_stats() or {} for d in devices]
    ctx.log(f"memory_stats of the first chip: {stats[0]}")
    # the TPU runtime keeps the programs' temporaries in a reservation apart
    # from the buffers in use (free = limit - in use - reserved), so the peak
    # a chip held is the sum of the two peaks
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  s.get("peak_bytes_in_use", 0)
                  + s.get("peak_bytes_reserved", 0) for s in stats)}
    result = {"correct": all(ctx.checks), "attempted": ctx.attempted,
              "failed": ctx.failed, "metrics": {}, "device": device}
    if not ctx.trace:
        for m in cell["end_to_end"]:
            if ctx.e2e.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {
                    "value": ctx.e2e[m["name"]], "unit": m["unit"]}
        result["checks"] = ctx.compared         # last on the line
        return result
    from chipbench.trace import reduce
    t_reduce = time.perf_counter()
    if dev.platform == "tpu":       # XLA:CPU has no peaks and no device plane
        ctx.peaks = flops.peaks(dev.device_kind)
        ctx.reduced = reduce.Trace(reduce.find_xplane(ctx.trace_dir))
        device["busy_s"] = ctx.reduced.busy_s()
        device["window_s"] = ctx.reduced.window_s
        result["breakdown"] = {"device_ops": ctx.reduced.device_ops(),
                               "idle_gaps": ctx.reduced.idle_gaps()}
    for m in cell["per_layer"]:
        spec = dict(m["file"]["reader"])
        value = readers.find(spec.pop("fn"))(ctx, **spec)
        if value is not None:
            result["metrics"][m["name"]] = {"value": float(value),
                                            "unit": m["unit"]}
    ctx.log(f"trace reduced and {len(cell['per_layer'])} per-layer metrics "
            f"read in {time.perf_counter() - t_reduce:.1f} s")
    result["checks"] = ctx.compared             # last on the line
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args()
    m = manifest.load(ROOT)
    errors = manifest.validate(m, ROOT)
    if errors:
        sys.exit("BENCHMARK.json is invalid:\n  " + "\n  ".join(errors))
    cell = manifest.cell(m, ROOT, args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        sys.exit(f"{args.workload} needs {cell['chips']} TPU chip(s); jax "
                 f"found {len(devices)} x {devices[0].device_kind} "
                 f"({devices[0].platform})")
    result = run_cell(cell, devices[:cell["chips"]], args.seed, args.seconds,
                      args.trace, t0=T0)
    print(json.dumps(result), flush=True)
    for key, c in result["checks"].items():
        print(f"[chipbench] {'ok' if c['ok'] else 'FAIL'} {key}: "
              f"{c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()


if __name__ == "__main__":
    main()
