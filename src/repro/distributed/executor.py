"""Mesh-native execution: ONE sharded substrate for prune -> eval -> serve.

Before this module, the mesh machinery lived only in ``distributed/``
(training) while the three user-facing pipelines each ran single-device:
calibration forwards on one chip, perplexity batches in a host loop,
every decode step on one device.  :class:`MeshExecutor` is the single
owner of mesh construction and placement that all three now share
(DESIGN.md §10):

* **prune** — Gram accumulation goes data-parallel over calibration
  micro-batches (per-shard Gram scan + one ``psum``, the pipeline's only
  collective), and FISTA group solves optionally row-shard over "model"
  through the existing ``distributed/rowfista`` path;
* **eval**  — perplexity / KL batches shard over "data": each device
  evaluates whole batches locally, per-batch scalars come back in batch
  order so the host-side reduction is bitwise-identical to the serial
  loop;
* **serve** — params place onto the mesh via the Megatron rules in
  ``distributed/sharding.py`` (column/row per block -> one all-reduce
  per block in decode) and the paged KV pool gains a heads-sharded
  device layout; GSPMD partitions the jitted decode step (the mesh's
  axes are Auto: ``launch/mesh.make_mesh``).

Determinism contract: XLA's CPU all-reduce is an ordered linear
reduction over the axis, so with one micro-batch per data shard the
psum-merged Gram statistics are **bitwise-equal** to the serial
left-fold (pinned in tests/distributed_cases.py).  With several batches
per shard the merge reassociates the fp32 sum and parity is ulp-level.

Everything here degrades gracefully: a :class:`MeshConfig` of 1x1 (or a
dimension that does not divide the workload) falls back to the exact
single-device code path, so the executor can be threaded unconditionally.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.distributed import rowfista, sharding
from repro.utils import get_logger

log = get_logger("executor")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """The strict ``mesh`` section of a ``PruneRecipe`` (and the value a
    launcher's ``--mesh dxm`` flag parses into).

    ``devices`` is the total device count the run expects (0 = all
    visible); ``data_parallel`` x ``model_parallel`` must factor it
    (``data_parallel`` 0 = derive from the other two).  A 1x1 config is
    the explicit "single device" request and builds no mesh.
    """

    devices: int = 0
    data_parallel: int = 0
    model_parallel: int = 1

    def __post_init__(self) -> None:
        for name in ("devices", "data_parallel", "model_parallel"):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 0:
                raise ValueError(f"mesh.{name} must be an int >= 0, got {v!r}")
        if self.model_parallel == 0:
            raise ValueError("mesh.model_parallel must be >= 1")

    @classmethod
    def parse(cls, spec: Any) -> "MeshConfig":
        """``"4x2"`` / ``"8"`` / ``{"devices": ...}`` / MeshConfig -> MeshConfig.

        The string form is ``DATAxMODEL`` (the launchers' ``--mesh`` flag);
        a bare integer means that many data shards with no model axis.
        """
        if isinstance(spec, cls):
            return spec
        if isinstance(spec, dict):
            return cls(**spec)
        text = str(spec).strip().lower()
        parts = text.split("x")
        try:
            if len(parts) == 1:
                d = int(parts[0])
                return cls(devices=d, data_parallel=d, model_parallel=1)
            if len(parts) == 2:
                d, m = int(parts[0]), int(parts[1])
                return cls(devices=d * m, data_parallel=d, model_parallel=m)
        except ValueError:
            pass
        raise ValueError(f"bad mesh spec {spec!r}; expected 'DATAxMODEL' "
                         f"(e.g. '4x2') or a device count")

    def resolve(self, available: Optional[int] = None) -> Tuple[int, int]:
        """(data, model) sizes against ``available`` devices; validates
        that the factorization matches the device count."""
        avail = jax.device_count() if available is None else available
        total = self.devices or (self.data_parallel * self.model_parallel
                                 if self.data_parallel else avail)
        data = self.data_parallel or max(total // self.model_parallel, 1)
        model = self.model_parallel
        if data * model != total:
            raise ValueError(
                f"mesh {data}x{model} does not factor devices={total}")
        if total > avail:
            raise ValueError(
                f"mesh {data}x{model} needs {total} devices, only "
                f"{avail} visible (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={total} on CPU)")
        return data, model

    @property
    def is_single(self) -> bool:
        return (self.model_parallel == 1 and self.data_parallel in (0, 1)
                and self.devices in (0, 1))

    def to_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


class MeshExecutor:
    """Owns one (data, model) mesh and every placement decision the
    three pipelines make against it.

    Built once per run (``api.prune`` / ``launch`` CLIs) and passed by
    object — it never serializes; the :class:`MeshConfig` it came from
    does.
    """

    def __init__(self, cfg: MeshConfig = MeshConfig(),
                 mesh: Optional[Mesh] = None):
        self.cfg = cfg
        if mesh is not None:
            self.mesh = mesh
        else:
            from repro.launch.mesh import make_mesh
            data, model = cfg.resolve()
            self.mesh = make_mesh((data, model), ("data", "model"))
        self.data_size = int(self.mesh.shape["data"])
        self.model_size = int(self.mesh.shape["model"])
        # jitted shard_map closures, keyed by call site: a fresh closure
        # per call would re-trace and re-compile the identical sharded
        # program every time (eval scores dense + pruned + KL per report;
        # the Gram scan runs per group x bucket x unit)
        self._jit_cache: Dict[Any, Callable] = {}

    def _cached(self, key: Any, build: Callable[[], Callable]) -> Callable:
        fn = self._jit_cache.get(key)
        if fn is None:
            fn = build()
            self._jit_cache[key] = fn
        return fn

    @classmethod
    def from_spec(cls, spec: Any) -> Optional["MeshExecutor"]:
        """Parse a ``--mesh`` flag value; None/empty/1x1 -> no executor."""
        if spec in (None, "", "1", "1x1"):
            return None
        cfg = MeshConfig.parse(spec)
        return None if cfg.is_single else cls(cfg)

    def describe(self) -> Dict[str, Any]:
        return {"data": self.data_size, "model": self.model_size,
                "devices": self.data_size * self.model_size}

    # ------------------------------------------------------------------
    # placement (GSPMD: NamedSharding via the Megatron rules)
    # ------------------------------------------------------------------
    def shard_params(self, params: Any) -> Any:
        """Place a param tree on the mesh per ``distributed/sharding.py``
        (column/row tensor parallelism over "model"; non-divisible dims
        and rule-less leaves — biases, norms, packed-2:4 stores —
        replicate via ``_fit_spec``)."""
        specs = sharding.param_specs(params)
        shardings = sharding.make_shardings(self.mesh, specs, params)
        return jax.device_put(params, shardings)

    def replicate(self, tree: Any) -> Any:
        return jax.device_put(
            tree, jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P()), tree))

    def shard_paged_pool(self, pool: Any, num_kv_heads: int) -> Any:
        """Heads-sharded device layout of the paged KV pool: the
        (L, num_blocks*block_size, nkv, hd) tensors shard ``nkv`` over
        "model", and the (L, num_blocks*block_size, nkv*hd) ones their
        rows, whole heads to a shard (each model shard holds its
        attention heads' pages — the decode gather/scatter is then fully
        local per shard and the one all-reduce per block lands after
        wo).  Falls back to replication when nkv does not divide the
        axis (MQA)."""
        whole = num_kv_heads % self.model_size == 0

        def spec(leaf):
            ndim = getattr(leaf, "ndim", 0)
            if ndim == 4:
                return sharding._fit_spec(self.mesh,
                                          P(None, None, "model", None),
                                          leaf.shape)
            if ndim == 3 and whole:
                return P(None, None, "model")
            return P()

        return jax.device_put(
            pool, jax.tree_util.tree_map(
                lambda l: NamedSharding(self.mesh, spec(l)), pool))

    def replicate_logits(self, logits: jnp.ndarray) -> jnp.ndarray:
        """Constrain sampling inputs to full replication.

        GSPMD happily leaves decode logits vocab-sharded (tied embeddings
        shard the vocab dim), but ``jax.random.categorical`` over a
        sharded operand draws DIFFERENT tokens than over the same values
        replicated — the partitioned RNG lowering is not value-identical.
        Every serving surface routes its logits through this constraint
        before sampling, which is what makes temperature-sampled TP
        decode token-identical to the single-device path.  Works both
        inside jit (``with_sharding_constraint``) and eagerly.
        """
        sh = NamedSharding(self.mesh, P())
        if isinstance(logits, jax.core.Tracer):
            return jax.lax.with_sharding_constraint(logits, sh)
        return jax.device_put(logits, sh)

    def shard_serve_state(self, state: Any) -> Any:
        """Contiguous serving caches (L, B, S, nkv, hd): shard heads over
        "model" (replicate everything non-5D / non-divisible)."""

        def spec(leaf):
            if getattr(leaf, "ndim", 0) == 5:
                return sharding._fit_spec(
                    self.mesh, P(None, None, None, "model", None), leaf.shape)
            return P()

        return jax.device_put(
            state, jax.tree_util.tree_map(
                lambda l: NamedSharding(self.mesh, spec(l)), state))

    # ------------------------------------------------------------------
    # prune: data-parallel Gram accumulation (one psum per group)
    # ------------------------------------------------------------------
    def can_shard_batches(self, num_batches: int) -> bool:
        return self.data_size > 1 and num_batches % self.data_size == 0

    def sharded_group_stats(self, scan_fn: Callable, init: Dict[str, Any],
                            current: Any, ws: Dict[str, jnp.ndarray],
                            dense_caps: Any, pruned_states: Any,
                            **static_kw: Any) -> Dict[str, Any]:
        """Data-parallel run of ``core.sequential._group_stats_scan``:
        every device scans ITS slice of the stacked calibration
        micro-batches from zero statistics, one ``psum`` over "data"
        merges, and the carried-in ``init`` is added on top.

        With one micro-batch per shard the psum's ordered reduction
        makes the result bitwise-equal to the serial scan (see module
        docstring); otherwise equal to fp32 round-off.  The carried-in
        ``init`` (nonzero when a group spans several shape buckets)
        seeds SHARD 0's scan rather than being added after the merge, so
        the association order matches the serial left-fold
        ``((init + g0) + g1) + ...`` exactly.
        """
        zeros = jax.tree_util.tree_map(jnp.zeros_like, init)

        def build():
            def local(ini, z, cur, w, caps, ps):
                first = jax.lax.axis_index("data") == 0
                start = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(first, a, b), ini, z)
                stats = scan_fn(start, cur, w, caps, ps, **static_kw)
                return jax.tree_util.tree_map(
                    lambda x: jax.lax.psum(x, "data"), stats)

            # prefix specs (structure-independent, so the jitted closure
            # is reusable across shape buckets of the same group)
            return jax.jit(jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(P(), P(), P(), P(), P("data"), P("data")),
                out_specs=P(),
                check_vma=False))  # psum outputs are replicated

        fn = self._cached(
            ("gram", scan_fn,
             tuple(sorted(static_kw.items(), key=lambda kv: kv[0]))), build)
        # span covers the sharded dispatch only (recording stays outside
        # the jitted body — OBS001); async dispatch returns immediately,
        # so `dur` measures launch overhead, not device seconds
        with obs.span("mesh.group_stats", data=self.data_size,
                      model=self.model_size):
            return fn(init, zeros, current, ws, dense_caps, pruned_states)

    # ------------------------------------------------------------------
    # prune: row-sharded FISTA solves over "model" (rowfista path)
    # ------------------------------------------------------------------
    def can_row_shard(self, rows: int) -> bool:
        return self.model_size > 1 and rows % self.model_size == 0

    def row_fista_solve(self, G: jnp.ndarray, B: jnp.ndarray, y0: jnp.ndarray,
                        lam, *, L, max_iters: int, tol: float,
                        momentum: str = "fista", step_impl: str = "jnp"
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """One FISTA solve with the m rows of (B, y0) sharded over
        "model" and G replicated — zero collectives per iteration
        (``distributed/rowfista.py``).  Same call/return contract as
        ``core.fista.solve`` so it drops into the Algorithm-1 host loop
        as its ``inner_solve`` (iteration count reported as the bound —
        per-shard early stopping is local)."""
        y = rowfista.sharded_solve(self.mesh, G, B, y0, lam, L,
                                   max_iters=max_iters, tol=tol,
                                   momentum=momentum, step_impl=step_impl)
        return y, jnp.int32(max_iters)

    # ------------------------------------------------------------------
    # eval: batch-sharded map over "data"
    # ------------------------------------------------------------------
    def data_map(self, fn: Callable[..., Any], stacked: Any,
                 *params: Any, cache_key: Any = None) -> Any:
        """Evaluate ``fn(batch, *params) -> pytree of scalars`` for every
        batch of a leading-axis-stacked batch tree, batches sharded over
        "data" and every ``params`` tree replicated.

        Each device evaluates WHOLE batches locally, so every per-batch
        value is the same fp32 number the serial loop produces; outputs
        come back stacked on the leading axis in batch order.  The
        caller's host-side reduction therefore matches the unsharded
        path bitwise.

        ``cache_key`` (e.g. ``(model, "ce")``) reuses the jitted sharded
        program across calls — callers passing a fresh ``fn`` lambda per
        call MUST pass a key describing its semantics, or every report
        re-traces (the sharded analog of the serial paths' per-model jit
        caches).
        """

        def build():
            def local(st, *ps):
                def body(_, b):
                    return None, fn(b, *ps)

                _, ys = jax.lax.scan(body, None, st)
                return ys

            return jax.jit(jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(P("data"),) + (P(),) * len(params),
                out_specs=P("data"),
                check_vma=False))

        mapped = build() if cache_key is None else \
            self._cached(("map", cache_key, len(params)), build)
        with obs.span("mesh.data_map", data=self.data_size,
                      key=str(cache_key)):
            return mapped(stacked, *params)
