"""Pallas kernel checker (rule family PAL, DESIGN.md §12).

Static inspection of ``pl.pallas_call`` sites by *capture*: the checker
monkeypatches ``pallas_call`` and runs each registered kernel wrapper on
small representative inputs under ``jax.disable_jit()``.  The recorder
never executes the kernel body — it grabs the grid, BlockSpecs, scratch
shapes and concrete operand shapes/dtypes, and returns zeros of
``out_shape`` so the wrapper's pad/slice epilogue still runs.  Index
maps are then *evaluated numerically* at every grid corner (with the
real scalar-prefetch arrays, so block-table indirection like
``tab[s, j]`` is checked against the actual pool extent).

PAL001  BlockSpec index map out of bounds for the declared grid: some
        grid corner maps a block outside the operand.
PAL002  Estimated VMEM footprint (double-buffered blocks + scratch,
        dtype-aware) exceeds the kernel's declared budget.
PAL003  Misaligned tile: a blocked (non-full-extent) lane dim not a
        multiple of 128, or a blocked sublane dim not 1 or a multiple
        of 8 — Mosaic pads these to full tiles, silently wasting VMEM
        and bandwidth.
PAL004  Kernel without a registered ``kernels/ref.py`` oracle + dispatch
        gate in ``kernels/ops.py`` — the bitwise fused-vs-oracle
        discipline (DESIGN.md §11) requires both.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import os
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .core import Finding

LANE = 128
SUBLANE = 8
_DOUBLE_BUFFER = 2


@dataclasses.dataclass
class PallasSite:
    """One captured ``pl.pallas_call`` invocation."""

    kernel_name: str
    grid: Tuple[int, ...]
    in_specs: List[Any]                      # pl.BlockSpec
    out_specs: List[Any]
    out_shapes: List[Any]                    # jax.ShapeDtypeStruct
    scratch_shapes: List[Any]                # pltpu.VMEM MemoryRefs
    num_scalar_prefetch: int
    # filled when the wrapper invokes the (fake) compiled kernel:
    operand_shapes: List[Tuple[Tuple[int, ...], Any]] = \
        dataclasses.field(default_factory=list)
    prefetch: List[np.ndarray] = dataclasses.field(default_factory=list)
    called: bool = False


@dataclasses.dataclass
class KernelCase:
    """One registered kernel: where it lives, its oracle, its VMEM budget
    and a builder that invokes the public wrapper on sample inputs."""

    name: str                      # registry name, e.g. "spmm24"
    path: str                      # repo-relative file for findings
    fn_name: str                   # public symbol ops.py must dispatch to
    oracle: str                    # kernels/ref.py oracle symbol
    vmem_budget: int               # bytes
    build: Callable[[], None]      # runs the wrapper under capture


def _as_seq(x: Any) -> List[Any]:
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


@contextlib.contextmanager
def capture_pallas() -> Iterator[List[PallasSite]]:
    """Patch ``pallas_call`` to record call structure instead of
    compiling; yields the list of captured sites."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    records: List[PallasSite] = []
    real = pl.pallas_call

    def recorder(kernel: Any, *, out_shape: Any, grid: Any = None,
                 in_specs: Any = None, out_specs: Any = None,
                 scratch_shapes: Any = (), grid_spec: Any = None,
                 **kw: Any) -> Callable[..., Any]:
        nps = 0
        if grid_spec is not None:
            grid = getattr(grid_spec, "grid", grid)
            in_specs = getattr(grid_spec, "in_specs", in_specs)
            out_specs = getattr(grid_spec, "out_specs", out_specs)
            scratch_shapes = getattr(grid_spec, "scratch_shapes",
                                     scratch_shapes)
            nps = int(getattr(grid_spec, "num_scalar_prefetch", 0))
        kname = getattr(kernel, "__name__", None) or getattr(
            getattr(kernel, "func", None), "__name__", "<kernel>")
        site = PallasSite(
            kernel_name=kname,
            grid=tuple(int(g) for g in _as_seq(grid)) or (1,),
            in_specs=_as_seq(in_specs),
            out_specs=_as_seq(out_specs),
            out_shapes=jax.tree_util.tree_leaves(
                out_shape, is_leaf=lambda x: hasattr(x, "shape")),
            scratch_shapes=_as_seq(scratch_shapes),
            num_scalar_prefetch=nps)
        records.append(site)

        def fake(*operands: Any) -> Any:
            site.called = True
            site.prefetch = [np.asarray(o) for o in operands[:nps]]
            site.operand_shapes = [
                (tuple(int(d) for d in np.shape(o)),
                 np.dtype(getattr(o, "dtype", np.asarray(o).dtype)))
                for o in operands[nps:]]
            return jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), out_shape,
                is_leaf=lambda x: hasattr(x, "shape"))

        return fake

    pl.pallas_call = recorder  # type: ignore[assignment]
    try:
        with jax.disable_jit():
            yield records
    finally:
        pl.pallas_call = real  # type: ignore[assignment]


# ---------------------------------------------------------------------------
# per-site checks
# ---------------------------------------------------------------------------
def _grid_corners(grid: Sequence[int]) -> Iterator[Tuple[int, ...]]:
    axes = [sorted({0, g - 1}) for g in grid]
    return itertools.product(*axes)


def _block_indices(spec: Any, idx: Tuple[int, ...],
                   prefetch: Sequence[np.ndarray]) -> Optional[Tuple[int, ...]]:
    imap = getattr(spec, "index_map", None)
    if imap is None:
        return None
    out = imap(*idx, *prefetch)
    if not isinstance(out, tuple):
        out = (out,)
    return tuple(int(b) for b in out)


def _check_one_spec(case: KernelCase, site: PallasSite, spec: Any,
                    array_shape: Tuple[int, ...], dtype: Any,
                    role: str, findings: List[Finding]) -> int:
    """Bounds + alignment for one BlockSpec; returns its VMEM bytes."""
    block = getattr(spec, "block_shape", None)
    if block is None:
        block = array_shape
    block = tuple(block)
    ctx = f"{case.name}.{site.kernel_name}"

    # --- PAL001: index map bounds at every grid corner -------------------
    for corner in _grid_corners(site.grid):
        try:
            bidx = _block_indices(spec, corner, site.prefetch)
        except Exception as e:  # index map itself blew up
            findings.append(Finding(
                rule="PAL001", path=case.path, line=0, context=ctx,
                detail=f"{role}:indexmap-error",
                message=f"{role} index map raised {type(e).__name__} at "
                        f"grid corner {corner}: {e}"))
            break
        if bidx is None:
            continue
        if len(bidx) != len(block):
            findings.append(Finding(
                rule="PAL001", path=case.path, line=0, context=ctx,
                detail=f"{role}:rank",
                message=f"{role} index map returns rank {len(bidx)} for "
                        f"block rank {len(block)}"))
            break
        for d, (b, bs) in enumerate(zip(bidx, block)):
            if bs is None:
                continue
            dim = array_shape[d] if d < len(array_shape) else 0
            nblocks = max(1, -(-dim // bs))  # ceil
            if b < 0 or b >= nblocks:
                findings.append(Finding(
                    rule="PAL001", path=case.path, line=0, context=ctx,
                    detail=f"{role}:dim{d}",
                    message=f"{role} index map sends grid corner {corner} "
                            f"to block {bidx}, but axis {d} has only "
                            f"{nblocks} block(s) of {bs} over extent "
                            f"{dim} — out of bounds"))

    # --- PAL003: tile alignment on the last two dims ---------------------
    concrete = [b for b in block if b is not None]
    if len(concrete) >= 1:
        lane_b = concrete[-1]
        lane_dim = array_shape[-1] if array_shape else lane_b
        if lane_b != lane_dim and lane_b % LANE != 0:
            findings.append(Finding(
                rule="PAL003", path=case.path, line=0, context=ctx,
                detail=f"{role}:lane",
                message=f"{role} lane (last) block dim {lane_b} is neither "
                        f"full-extent ({lane_dim}) nor a multiple of "
                        f"{LANE} — Mosaic pads the tile"))
    if len(concrete) >= 2:
        sub_b = concrete[-2]
        sub_dim = array_shape[-2] if len(array_shape) >= 2 else sub_b
        if sub_b != sub_dim and sub_b != 1 and sub_b % SUBLANE != 0:
            findings.append(Finding(
                rule="PAL003", path=case.path, line=0, context=ctx,
                detail=f"{role}:sublane",
                message=f"{role} sublane block dim {sub_b} is neither "
                        f"full-extent ({sub_dim}), 1, nor a multiple of "
                        f"{SUBLANE}"))

    bytes_ = int(np.prod([b for b in block if b is not None], dtype=np.int64)
                 ) * np.dtype(dtype).itemsize
    return bytes_ * _DOUBLE_BUFFER


def check_site(case: KernelCase, site: PallasSite) -> List[Finding]:
    findings: List[Finding] = []
    ctx = f"{case.name}.{site.kernel_name}"
    if not site.called:
        findings.append(Finding(
            rule="PAL001", path=case.path, line=0, context=ctx,
            detail="not-called",
            message="pallas_call captured but the wrapper never invoked "
                    "it — sample inputs don't exercise this site"))
        return findings
    if len(site.in_specs) != len(site.operand_shapes):
        findings.append(Finding(
            rule="PAL001", path=case.path, line=0, context=ctx,
            detail="arity",
            message=f"{len(site.in_specs)} in_specs for "
                    f"{len(site.operand_shapes)} (non-prefetch) operands"))
        return findings

    vmem = 0
    for i, (spec, (shape, dtype)) in enumerate(
            zip(site.in_specs, site.operand_shapes)):
        vmem += _check_one_spec(case, site, spec, shape, dtype,
                                f"in[{i}]", findings)
    for i, (spec, struct) in enumerate(zip(site.out_specs, site.out_shapes)):
        vmem += _check_one_spec(case, site, spec,
                                tuple(struct.shape), struct.dtype,
                                f"out[{i}]", findings)
    for ref in site.scratch_shapes:
        if str(getattr(ref, "memory_space", "vmem")) != "vmem":
            continue            # semaphores and SMEM scratch hold no VMEM
        vmem += int(np.prod(tuple(ref.shape), dtype=np.int64)) * \
            np.dtype(ref.dtype).itemsize

    if vmem > case.vmem_budget:
        findings.append(Finding(
            rule="PAL002", path=case.path, line=0, context=ctx,
            detail="vmem",
            message=f"estimated VMEM {vmem / 2**20:.2f} MiB (double-"
                    f"buffered blocks + scratch) exceeds the "
                    f"{case.vmem_budget / 2**20:.2f} MiB budget"))
    return findings


def check_kernel_case(case: KernelCase) -> List[Finding]:
    """Capture + check every pallas_call the case's builder reaches."""
    try:
        with capture_pallas() as sites:
            case.build()
    except Exception as e:
        return [Finding(
            rule="PAL001", path=case.path, line=0, context=case.name,
            detail="build-error",
            message=f"kernel builder failed under capture: "
                    f"{type(e).__name__}: {e}")]
    if not sites:
        return [Finding(
            rule="PAL001", path=case.path, line=0, context=case.name,
            detail="no-sites",
            message="builder ran but no pallas_call was captured")]
    out: List[Finding] = []
    for site in sites:
        out += check_site(case, site)
    return out


def check_oracle_gate(case: KernelCase, ops_source: str) -> List[Finding]:
    """PAL004: ops.py must reference both the ref oracle and the kernel's
    public symbol (the dispatch gate)."""
    findings: List[Finding] = []
    if f"ref.{case.oracle}" not in ops_source:
        findings.append(Finding(
            rule="PAL004", path=case.path, line=0, context=case.name,
            detail="oracle",
            message=f"kernels/ops.py never references ref.{case.oracle} — "
                    f"no registered oracle for {case.name}"))
    if case.fn_name not in ops_source:
        findings.append(Finding(
            rule="PAL004", path=case.path, line=0, context=case.name,
            detail="gate",
            message=f"kernels/ops.py never references {case.fn_name} — "
                    f"no dispatch gate for {case.name}"))
    return findings


# ---------------------------------------------------------------------------
# the registry: every shipped kernel with representative decode-ish shapes
# ---------------------------------------------------------------------------
def _build_spmm24() -> None:
    import jax.numpy as jnp
    from repro.kernels import spmm24 as mod
    x = jnp.zeros((8, 2048), jnp.float32)
    vals = jnp.zeros((512, 1024), jnp.float32)
    meta = jnp.zeros((512, 512), jnp.uint8)
    mod.spmm24(x, vals, meta, 2048)


def _build_round24() -> None:
    import jax.numpy as jnp
    from repro.kernels import round24 as mod
    mod.round24(jnp.zeros((512, 4096), jnp.float32))


def _build_fista() -> None:
    import jax.numpy as jnp
    from repro.kernels import fista_step as mod
    y = jnp.zeros((512, 1024), jnp.float32)
    G = jnp.zeros((1024, 1024), jnp.float32)
    B = jnp.zeros((512, 1024), jnp.float32)
    mod.fista_prox_step(y, G, B, 0.1, 0.01)


def _build_flash() -> None:
    import jax.numpy as jnp
    from repro.kernels import flash_attention as mod
    q = jnp.zeros((1, 4, 256, 128), jnp.float32)
    kv = jnp.zeros((1, 2, 256, 128), jnp.float32)
    mod.flash_attention(q, kv, kv, causal=True, window=64)


def _build_paged() -> None:
    import jax.numpy as jnp
    from repro.kernels import paged_attention as mod
    S, nq, nkv, hd, bs, nblocks = 2, 8, 2, 128, 8, 8
    q = jnp.zeros((S, nq, hd), jnp.float32)
    pool = jnp.zeros((nblocks * bs, nkv, hd), jnp.float32)
    tables = jnp.asarray([[0, 1, 2, 7], [3, 4, 5, 6]], jnp.int32)
    pos = jnp.asarray([5, 9], jnp.int32)
    active = jnp.asarray([1, 1], jnp.int32)
    mod.paged_decode_attn(q, pool, pool, tables, pos, active, block_size=bs)


def _build_paged_lanes() -> None:
    import jax.numpy as jnp
    from repro.kernels import paged_attention as mod
    S, nq, nkv, hd, bs, nblocks = 2, 4, 2, 64, 16, 8
    q = jnp.zeros((S, nq, hd), jnp.float32)
    pool = jnp.zeros((nblocks * bs, nkv * hd), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 7]], jnp.int32)
    pos = jnp.asarray([40, 63], jnp.int32)
    active = jnp.asarray([1, 1], jnp.int32)
    mod.paged_decode_attn_lanes(q, pool, pool, tables, pos, active,
                                block_size=bs, blocks=2)


def _build_fused_mlp() -> None:
    import jax.numpy as jnp
    from repro.kernels import paged_attention as mod
    B, d, f = 4, 512, 1024
    w1v = jnp.zeros((f, d // 2), jnp.float32)
    w1m = jnp.zeros((f, d // 4), jnp.uint8)
    w2v = jnp.zeros((d, f // 2), jnp.float32)
    w2m = jnp.zeros((d, f // 4), jnp.uint8)
    x = jnp.zeros((B, d), jnp.float32)
    mod.fused_mlp24(x, w1v, w1m, None, w1v, w1m, w2v, w2m, None)


KERNEL_CASES: List[KernelCase] = [
    KernelCase("spmm24", "src/repro/kernels/spmm24.py", "spmm24",
               "spmm24", 4 * 2**20, _build_spmm24),
    KernelCase("round24", "src/repro/kernels/round24.py", "round24",
               "round24", 8 * 2**20, _build_round24),
    KernelCase("fista_step", "src/repro/kernels/fista_step.py",
               "fista_prox_step", "fista_prox_step", 4 * 2**20, _build_fista),
    KernelCase("flash_attention", "src/repro/kernels/flash_attention.py",
               "flash_attention", "flash_attention", 6 * 2**20, _build_flash),
    KernelCase("paged_attention", "src/repro/kernels/paged_attention.py",
               "paged_decode_attn", "paged_attention", 4 * 2**20,
               _build_paged),
    KernelCase("paged_attention_lanes",
               "src/repro/kernels/paged_attention.py",
               "paged_decode_attn_lanes", "paged_attention", 4 * 2**20,
               _build_paged_lanes),
    KernelCase("fused_mlp24", "src/repro/kernels/paged_attention.py",
               "fused_mlp24", "fused_mlp24", 8 * 2**20, _build_fused_mlp),
]


def check_kernels(root: str = ".",
                  cases: Optional[List[KernelCase]] = None) -> List[Finding]:
    """Run the full Pallas family over the registered kernels."""
    cases = KERNEL_CASES if cases is None else cases
    ops_path = os.path.join(root, "src", "repro", "kernels", "ops.py")
    try:
        with open(ops_path, "r", encoding="utf-8") as fh:
            ops_source = fh.read()
    except OSError:
        ops_source = ""
    findings: List[Finding] = []
    for case in cases:
        findings += check_kernel_case(case)
        findings += check_oracle_gate(case, ops_source)
    return findings
