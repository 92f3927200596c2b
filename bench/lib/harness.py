"""What every cell shares: files found by name, the device, the result.

A cell ``<name>`` in ``BENCHMARK.json`` names its configuration and its
traffic mix; the harness reads ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json`` and ``bench/workloads/<name>.json``,
and a per-layer metric ``<metric>`` is read by
``bench/metrics/<metric>.py``.  Adding any of them adds files only.

Everything about a configuration's layer layout lives in its plain
reference (``"reference"`` in the file): ``dims`` gives the sizes, and
may give the layer kinds the work counts sum over (``lib/work``);
``layout`` the seeded tree's leaves (``lib/weights``); ``program_sizes``
the program's config attributes to check (``build_model``).
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import re
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
    """The configuration's plain reference: ``"reference": "<name>"`` is
    ``configs/<name>.py``, beside the file; a name with a ``/`` is a
    path under ``bench/`` without the ``.py``."""
    name = conf["reference"]
    path = os.path.join(BENCH, *name.split("/")) if "/" in name else \
        os.path.join(BENCH, "configs", name)
    return load_module(path + ".py",
                       "bench_ref_" + re.sub(r"\W", "_", name))


def ref_dims(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's sizes, with ``"leaves"``: (path, shape, role,
    dtype) of every leaf of its layout, which the work counts read."""
    from lib import weights
    ref = reference(conf)
    dm = ref.dims(conf)
    return dict(dm, leaves=weights.leaves(
        dm, conf.get("enable_bias", False), ref.layout))


def program_value(cfg: Any, dotted: str) -> Any:
    """``cfg.<dotted attribute>``; a method is called.  An attribute the
    config lacks is an error."""
    value = cfg
    for part in dotted.split("."):
        if not hasattr(value, part):
            raise ValueError(f"the program's config has no {dotted!r}")
        value = getattr(value, part)
    return value() if callable(value) else value


def build_model(conf: Dict[str, Any]):
    """The system's ModelDef for a configuration file, built through the
    registry, and checked against the file's sizes.

    ``model_overrides`` replaces fields of the program's config; an
    object replaces fields of the nested config under its key
    (``{"moe": {"num_experts": 8}}``).  The sizes checked are the
    reference's ``program_sizes(dm, bias)`` (dotted attribute -> value)
    and the dtype."""
    from repro.models.registry import model_def
    mod = importlib.import_module(
        "repro.configs." + conf["arch"].replace("-", "_").replace(".", "_"))
    cfg = mod.config()
    over = {}
    for key, value in conf.get("model_overrides", {}).items():
        if isinstance(value, dict):
            nested = getattr(cfg, key, None)
            if not dataclasses.is_dataclass(nested):
                raise ValueError(f"{conf['name']}: model_overrides.{key} "
                                 f"names no nested config ({nested!r})")
            value = dataclasses.replace(nested, **value)
        over[key] = value
    cfg = cfg.replace(**over)
    ref = reference(conf)
    dm = ref.dims(conf)
    want = ref.program_sizes(dm, conf.get("enable_bias", False))
    bad = {k: (got, v) for k, v in want.items()
           if (got := program_value(cfg, k)) != v}
    dtype = conf.get("assumed", {}).get("torch_dtype", conf["torch_dtype"])
    if bad or cfg.param_dtype != dtype:
        raise ValueError(f"{conf['name']}: the program's config departs from "
                         f"the file: {bad} (program, file), dtype "
                         f"{cfg.param_dtype}")
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
