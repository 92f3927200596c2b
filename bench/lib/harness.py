"""What every cell shares: files found by name, the device, the result.

A cell ``<name>`` in ``BENCHMARK.json`` names its configuration and its
traffic mix; the harness reads ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json`` and ``bench/workloads/<name>.json``,
and a per-layer metric ``<metric>`` is read by
``bench/metrics/<metric>.py``.  Adding any of them adds files only.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Tuple

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def read_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return read_json(ROOT, "BENCHMARK.json")


def cell_files(name: str) -> Tuple[Dict, Dict, Dict, Dict]:
    """(BENCHMARK.json entry, workload file, configuration, traffic mix)."""
    entry = next((w for w in benchmark()["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = read_json(BENCH, "workloads", f"{name}.json")
    conf = read_json(BENCH, "configs", f"{entry['config']}.json")
    mix = read_json(BENCH, "traffic", f"{entry['traffic']}.json")
    return entry, cell, conf, mix


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(conf: Dict[str, Any]):
    """The configuration's plain reference, beside it in ``configs/``."""
    return load_module(os.path.join(BENCH, "configs",
                                    conf["reference"] + ".py"),
                       "bench_ref_" + conf["reference"])


def ref_dims(conf: Dict[str, Any]) -> Dict[str, Any]:
    return reference(conf).dims(conf)


def build_model(conf: Dict[str, Any]):
    """The system's ModelDef for a configuration file, built through the
    registry, and checked against the file's sizes."""
    from repro.models.registry import model_def
    mod = importlib.import_module(
        "repro.configs." + conf["arch"].replace("-", "_").replace(".", "_"))
    cfg = mod.config().replace(**conf.get("model_overrides", {}))
    dm = ref_dims(conf)
    got = {"layers": cfg.num_layers, "d": cfg.d_model, "heads": cfg.num_heads,
           "kv_heads": cfg.num_kv_heads, "head_dim": cfg.resolved_head_dim(),
           "d_ff": cfg.d_ff, "vocab": cfg.vocab, "norm": cfg.norm,
           "act": cfg.act, "theta": float(cfg.rope_theta),
           "tie": cfg.tie_embeddings}
    bad = {k: (v, dm[k]) for k, v in got.items() if v != dm[k]}
    dtype = conf.get("assumed", {}).get("torch_dtype", conf["torch_dtype"])
    if bad or cfg.param_dtype != dtype or cfg.qkv_bias != bool(
            conf.get("enable_bias", False)):
        raise ValueError(f"{conf['name']}: the program's config departs from "
                         f"the file: {bad}, dtype {cfg.param_dtype}, "
                         f"qkv_bias {cfg.qkv_bias}")
    return model_def(cfg)


def device_info(chips: int) -> Dict[str, Any]:
    """Platform, kind and count as JAX reports them; refuses no TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peaks(kind: str) -> Dict[str, Any]:
    table = read_json(BENCH, "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def memory_peak(chips: int) -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


class CompileCounter:
    """Counts the programs compiled, or read from the persistent
    compilation cache, while ``on``: a warmed-up window counts none."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        from jax import monitoring
        self.on, self.count = False, 0
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name: str, _secs: float, **_kw) -> None:
        if self.on and name in self.EVENTS:
            self.count += 1

    def __enter__(self):
        self.on = True
        return self

    def __exit__(self, *exc):
        self.on = False


def metric_reader(name: str):
    return load_module(os.path.join(BENCH, "metrics", f"{name}.py"),
                       "bench_metric_" + name.replace(".", "_"))


def per_layer(cell_name: str, kind_metrics: List[Dict[str, Any]],
              ctx: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Every per-layer metric that lists this cell (or lists none) and
    whose reader finds something to read."""
    out = {}
    for m in kind_metrics:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        v = metric_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def end_to_end(bench: Dict[str, Any], cell_name: str,
               values: Dict[str, float]) -> Dict[str, Dict[str, Any]]:
    """The cell's end-to-end metrics: those that list it, or list none."""
    return {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])}


def check_lines(checks: List[Tuple[str, float, float, str]]) -> Tuple[bool, List[str]]:
    """``checks``: (name, value, limit, "<=" | "=="); all must hold."""
    ok, lines = True, []
    for name, value, limit, op in checks:
        good = value <= limit if op == "<=" else value == limit
        ok &= bool(good)
        lines.append(f"{name} {value!r} limit {op} {limit!r} "
                     f"{'ok' if good else 'FAIL'}")
    return ok, lines


def emit(result: Dict[str, Any], checks_text: List[str]) -> None:
    for line in checks_text:
        print("check: " + line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
