"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json``: ``bench/configs/<config>.json`` holds the sizes as
run and names its model in a ``"model"`` key, ``bench/models/<model>.py``
that model's sizes, seeded weights, float32 reference and counts of
operations and bytes, ``bench/traffic/<mix>.json`` the parameters of the
one generator, ``bench/limits/<cell>.json`` the limits of the
correctness comparison, and ``bench/metrics/<metric>.py`` one reader
per per-layer metric.

With ``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the line carries
the per-layer metrics, the device's busy and window seconds and a
breakdown.  Off a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent / "src"))

import core  # noqa: E402


class Tracer:
    """The profiler around the measured window, into a scratch dir."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no per-call Python events
        opts.host_tracer_level = 2        # keeps the bench.* spans
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def summary(self, chips: int):
        import trace_reduce
        try:
            return trace_reduce.reduce_file(
                trace_reduce.find_xplane(self.dir), chips=chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def end_to_end(spec, cell, data, setup_s: float) -> dict:
    import serve_cell
    units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
    values = {"setup_s": setup_s, "out_tok_s": serve_cell.out_tok_s(data)}
    want = [m["name"] for m in core.cell_metrics(spec, cell["name"], False)]
    return {k: {"value": values[k], "unit": units[k]} for k in want}


def per_layer(spec, cell, data) -> dict:
    out = {}
    for m in core.cell_metrics(spec, cell["name"], True):
        v = core.load_reader(m["name"])(data)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def measure(argv=None, tracer_type=Tracer):
    """One run of the cell that the arguments name: (result line,
    compared numbers, RunData), or None where it cannot be measured
    here.  ``tracer_type`` makes the profiler of a traced run."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = core.load_spec()
    cell, cfg_file, model = core.find_cell(spec, args.workload)
    try:
        core.require_chips(int(cell["chips"]))
    except core.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return None
    cache = core.enable_compile_cache()
    counter = core.CompileCounter()
    core.log("start", workload=cell["name"], seed=args.seed,
             seconds=args.seconds, trace=args.trace, compile_cache=cache)

    import serve_cell
    tracer = tracer_type() if args.trace else None
    result, compared, data = serve_cell.run(
        cell, cfg_file, model, args.seed, args.seconds, T_START, counter,
        tracer=tracer)
    setup_s = result.pop("setup_s")
    if tracer is not None:
        t = time.perf_counter()
        data.trace = tracer.summary(int(cell["chips"]))
        result["device"]["busy_s"] = data.trace.busy_s
        result["device"]["window_s"] = data.trace.window_s
        result["metrics"] = per_layer(spec, cell, data)
        result["breakdown"] = data.trace.breakdown()
        core.log("trace", reduce_s=time.perf_counter() - t,
                 busy_s=data.trace.busy_s, window_s=data.trace.window_s,
                 programs=data.trace.module_s,
                 top_ops=sorted(data.trace.op_s.items(),
                                key=lambda kv: -kv[1])[:12])
    else:
        result["metrics"] = end_to_end(spec, cell, data, setup_s)
    core.log("done", setup_s=setup_s,
             process_s=time.perf_counter() - T_START)
    out = {k: result[k] for k in ("correct", "attempted", "failed",
                                   "metrics", "device")}
    if "breakdown" in result:
        out["breakdown"] = result["breakdown"]
    return out, compared, data


def main() -> int:
    got = measure()
    if got is None:
        return 1
    core.emit(*got[:2])
    return 0


if __name__ == "__main__":
    sys.exit(main())
