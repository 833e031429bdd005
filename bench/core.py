"""Plumbing shared by every cell: the cell table, the model module a
configuration names, the chip check, the compile cache, compile
counting, the table of peaks, the metric readers and the result line.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
# bench/models/<model>.py, one per model family a configuration can name
MODELS = BENCH / "models"
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")

# tracing, lowering and compiling (or loading a compiled program from the
# persistent cache) each report one of these events; a run counts them
# inside its measured window, where there must be none
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(SystemExit):
    """The run cannot be measured here: no accelerator, or too few."""


def log(tag: str, **fields):
    """An earlier line of the run's standard output."""
    print(f"[{tag}] {json.dumps(fields, default=float)}", flush=True)


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_cell(spec: dict, name: str):
    """The cell's entry, its configuration file and the model module
    that file names (the run stops here if it names none)."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg_file = json.loads((ROOT / cfg["file"]).read_text())
    return cell, cfg_file, load_model(cfg_file, cfg["file"])


def load_model(cfg_file: dict, where: str):
    """The module ``bench/models/<model>.py`` that the configuration's
    ``"model"`` key names: its sizes, seeded weights, float32 reference
    and counts of operations and bytes.  ``where`` names the file in
    the message that stops a run whose configuration names none."""
    name = cfg_file.get("model")
    if not isinstance(name, str) or not _NAME.fullmatch(name):
        raise SystemExit(f"{where}: needs a \"model\" key naming a module "
                         f"of {MODELS}, found {name!r}")
    path = MODELS / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"{where}: model {name!r} has no module {path}")
    return _load(path, "bench_model_" + name)


def cell_metrics(spec: dict, cell: str, trace: bool):
    """The metric entries this cell reports in this kind of run."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def require_chips(n: int):
    """Fail the run (no result printed) unless JAX sees n TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found platform {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"needs {n} chips, JAX found {len(devs)}")


def enable_compile_cache():
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` names one.  Every
    program is cached, however quickly it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts compile events (and their seconds) while ``armed``."""

    def __init__(self):
        import jax
        self.armed = False
        self.events = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event in COMPILE_EVENTS:
            self.events += 1
            self.seconds += duration


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r}; "
                         f"add it to bench/peaks.json with its source")
    return table[device_kind]


def device_info(n_chips: int) -> dict:
    import jax
    devs = jax.devices()[:n_chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def load_reader(name: str):
    """The per-layer metric reader ``bench/metrics/<name>.py``."""
    return _load(BENCH / "metrics" / f"{name}.py",
                 "bench_metric_" + name).read


def _load(path: Path, module: str):
    """Execute one file of the benchmark as a module of its own; its
    directory is put on the path for the helpers it imports."""
    if str(path.parent) not in sys.path:
        sys.path.insert(0, str(path.parent))
    spec = importlib.util.spec_from_file_location(
        module.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def emit(result: dict, compared: dict):
    """Print the compared numbers (last lines of standard error) and the
    result line (last line of standard output, ``compared`` last)."""
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r} "
              f"({c['rule']})", file=sys.stderr, flush=True)
    out = dict(result)
    out["compared"] = compared
    print(json.dumps(out, default=float), flush=True)
