"""What every cell's run shares: finding its files by name, the device
check, the compile cache, compile counting, and the result line.

Nothing here knows a configuration, a traffic mix or a metric by name:
``BENCHMARK.json`` names them and the files under ``bench/`` hold them.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
RESULTS = ROOT / "results" / "bench"
# where a configuration's ``reference`` names its architecture module
ARCHITECTURES = BENCH / "reference"


def process_age_s() -> float:
    """Seconds since this process was started (Linux /proc), so that set-up
    counts the interpreter's own start."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start_ticks = int(fields[19])                # field 22 of stat
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


_AGE0 = process_age_s()
_PC0 = time.perf_counter()


def age() -> float:
    """Seconds since the process started, on the perf counter's clock."""
    return _AGE0 + time.perf_counter() - _PC0


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(name: str) -> dict:
    """The cell's entries from ``BENCHMARK.json``, with the files it names
    loaded: ``workload``, ``config`` (the configuration file), ``traffic``
    (``bench/traffic/<traffic>.json``), ``limits``
    (``bench/limits/<cell>.json``), and the metric entries it reports."""
    spec = load_json(ROOT / "BENCHMARK.json")
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]

    def mine(m):
        return name in m.get("workloads", [name])

    return {
        "workload": w,
        "config": load_json(ROOT / conf["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        "limits": load_json(BENCH / "limits" / f"{name}.json"),
        "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
        "per_layer": [m for m in spec["per_layer"] if mine(m)],
    }


def require_chips(n: int):
    """The devices of a TPU run with at least ``n`` chips; anything else
    exits non-zero before a result is printed."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < n:
        raise SystemExit(f"needs {n} chips; JAX found {len(devices)}")
    return devices[:n]


def add_src():
    """The system under test's sources on the path."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def setup_jax():
    """The checkout's persistent compile cache, every program in it, and the
    program's sources on the path."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    add_src()


class CompileCounter:
    """Counts XLA compilations (a persistent-cache load included) and cache
    hits and misses, so a run can show that nothing compiled in its
    window."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.hits, "cache_misses": self.misses}


def device_info(devices, peak_bytes=None) -> dict:
    d = devices[0]
    if peak_bytes is None:
        # the CPU of the tests' runs reports no memory statistics
        peak_bytes = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
                         for x in devices)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak_bytes)}


def _load(prefix: str, name: str, path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"no {path} for {name!r}")
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(art) -> float | None``."""
    return _load("bench_metric_", name,
                 BENCH / "metrics" / f"{name}.py").read


def architecture(conf: dict):
    """The architecture module that configuration ``conf`` names under
    ``reference``: ``<ARCHITECTURES>/<reference>.py``, with ``init(key, m)``
    and ``loss(p, m, tokens, labels)`` (the plain float32 reference),
    ``train_flops_per_token(m)``, ``SCOPES`` (the program's named scopes
    inside ``model``) and ``CPU_SIZE`` (the model keys a CPU test shrinks,
    with their values), where ``m`` is the configuration's ``model``.
    ``m["vocab_size"]`` bounds the token rows (bench/traffic/tokens.py)."""
    name = conf["reference"]
    return _load("bench_arch_", name, ARCHITECTURES / f"{name}.py")


def read_per_layer(entries, art) -> dict:
    """Every per-layer metric that ``BENCHMARK.json`` lists for the cell.
    A reader returns None where it finds nothing to read; for a metric
    listed for this cell that is a fault of the run or of the reader, and
    the run fails rather than leave the metric out."""
    out = {}
    for m in entries:
        value = load_metric_reader(m["name"])(art)
        if value is None:
            raise RuntimeError(f"per-layer metric {m['name']} found nothing "
                               f"to read in a cell it is listed for")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """``readings``: name -> number compared; ``limits``: name -> limit.
    Correct when every number is at or under its limit; a number that is
    missing or not finite is not correct."""
    import math
    table, ok = {}, True
    for name, limit in limits.items():
        v = readings.get(name)
        good = v is not None and math.isfinite(v) and v <= limit
        ok &= good
        table[name] = {"value": v, "limit": limit}
    return ok, table


def emit_result(result: dict, checks: dict) -> None:
    """The checks on standard error as its last lines, then the result line
    as the last line of standard output, with the checks under the key
    that comes last."""
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    result = dict(result, checks=checks)
    print(json.dumps(result), flush=True)


def out_dir(cell: str, seed: int, trace: bool) -> Path:
    d = RESULTS / cell / f"seed{seed}{'-trace' if trace else ''}"
    d.mkdir(parents=True, exist_ok=True)
    return d
