"""Block-size autotuner for the Pallas kernels (DESIGN.md Sec. 12).

The seed-era wrappers hardcoded 128x128 tiles.  That is the *floor*
the MXU imposes, not the optimum: for large operands bigger tiles
amortize grid overhead and raise MXU occupancy, while for small
operands a 512-wide tile only pads.  This module owns the choice:

``tuned_blocks(op, dims, ...)`` returns the tile sizes ops.py launches
with, resolved in three tiers:

1. **cache hit** — a per-process table keyed on
   ``(op, dims, dtype, kind)`` (the issue's per-(shape, dtype, kind)
   contract).  Tile choice is deterministic per key, which is what
   keeps jit caches warm: the same substrate shapes always resolve to
   the same static block arguments, so a value-equal substrate
   re-trace hits the existing executable (the recompile-regression
   test pins this with ``telemetry.probe.CompileCounter``).
2. **interpret-safe defaults (CPU)** — off-TPU the kernels run in
   interpret mode, where "timing" tiles measures the Python
   interpreter, so no search runs: the default is the alignment floor
   (128) clipped to the padded operand, recorded in the cache with
   ``source="default"``.
3. **measured search (TPU)** — on a real TPU backend, and only when
   no operand is a tracer (a search launches kernels; doing that while
   tracing an outer jit would nest tracers), each aligned candidate
   tile is compiled by the caller-provided ``compile_candidate`` and
   timed, and the fastest wins (``source="search"``).  A candidate the
   TPU compiler refuses (a tiling it cannot lower, more VMEM than the
   kernel may use) is skipped; if it refuses every candidate the
   search raises instead of hiding a kernel that cannot run.

The cache is process-local on purpose.  Persisting tunings across
processes would couple benchmark runs to stale machine profiles; a
process re-tunes once per distinct shape, which for this workload
(a handful of static substrate shapes per run) is noise.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax

_ALIGN = 128                      # MXU/VPU alignment floor
_CANDIDATES = (128, 256, 512)     # aligned tile candidates per axis
_SEARCH_ITERS = 3                 # timing iterations per candidate


@dataclasses.dataclass(frozen=True)
class TileKey:
    """Cache key: one tile choice per (op, operand dims, dtype, kind)."""

    op: str
    dims: Tuple[int, ...]
    dtype: str
    kind: str


@dataclasses.dataclass(frozen=True)
class TileChoice:
    """A resolved tile assignment and where it came from."""

    blocks: Tuple[int, ...]
    source: str                   # "default" | "search" | "pinned"


_CACHE: Dict[TileKey, TileChoice] = {}


def _round_up(n: int, mult: int = _ALIGN) -> int:
    return ((n + mult - 1) // mult) * mult


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _tracing(operands: Sequence[object]) -> bool:
    """True when any operand is a tracer: the op is being traced into an
    outer jit/vmap/scan, where a search would launch kernels inside the
    outer trace, so the resolver must not."""
    return any(isinstance(x, jax.core.Tracer)
               for x in jax.tree.leaves(operands))


def candidates_for(size: int) -> Tuple[int, ...]:
    """Aligned tile sizes worth trying for one axis of extent ``size``:
    every candidate <= the padded extent (a tile larger than the padded
    operand only adds zero rows), always including the 128 floor."""
    padded = _round_up(max(int(size), 1))
    return tuple(c for c in _CANDIDATES if c <= padded) or (_ALIGN,)


def default_blocks(dims: Sequence[int]) -> Tuple[int, ...]:
    """The no-search choice: the alignment floor per axis (clipped via
    candidates_for so a 40-row operand gets a 128 tile, not 512)."""
    return tuple(candidates_for(s)[0] for s in dims)


# What the TPU compiler raises when it refuses one candidate: the Pallas
# lowering's ValueError (block shape / tiling) or the compile's runtime
# error (e.g. scoped VMEM exceeded).
_REFUSALS = (ValueError, jax.errors.JaxRuntimeError)


def _time_thunk(thunk: Callable[[], object]) -> float:
    jax.block_until_ready(thunk())          # compile + warm
    t0 = time.perf_counter()
    for _ in range(_SEARCH_ITERS):
        jax.block_until_ready(thunk())
    return time.perf_counter() - t0


def tuned_blocks(
    op: str,
    dims: Sequence[int],
    *,
    dtype: str = "float32",
    kind: str = "",
    compile_candidate: Optional[
        Callable[[Tuple[int, ...]], Callable[[], object]]] = None,
    operands: Sequence[object] = (),
) -> Tuple[int, ...]:
    """The tile sizes to launch ``op`` with for operand extents ``dims``
    (one entry per blocked axis).

    ``compile_candidate(blocks)`` — when provided and the backend is a
    real TPU — must compile the kernel with the given tile assignment
    and return a thunk that launches it and returns a value to block
    on; the resolver times each candidate assignment and caches the
    winner.  Off-TPU (interpret mode) or when any of ``operands`` is a
    tracer, the defaults are cached without any launch.
    """
    key = TileKey(op=op, dims=tuple(int(s) for s in dims),
                  dtype=str(dtype), kind=str(kind))
    hit = _CACHE.get(key)
    if hit is not None:
        return hit.blocks

    if (compile_candidate is None or not _on_tpu()
            or _tracing(operands)):
        choice = TileChoice(default_blocks(key.dims), "default")
        _CACHE[key] = choice
        return choice.blocks

    best, best_t = None, float("inf")
    refused = []
    for blocks in _grid(tuple(candidates_for(s) for s in key.dims)):
        try:
            thunk = compile_candidate(blocks)
        except _REFUSALS as e:   # e.g. a tile that exceeds VMEM
            refused.append(f"{blocks}: {e}")
            continue
        t = _time_thunk(thunk)
        if t < best_t:
            best, best_t = blocks, t
    if best is None:
        raise RuntimeError(
            f"the TPU compiler refused every tile candidate for {key}:\n"
            + "\n".join(refused))
    choice = TileChoice(best, "search")
    _CACHE[key] = choice
    return choice.blocks


def _grid(axes: Tuple[Tuple[int, ...], ...]) -> Tuple[Tuple[int, ...], ...]:
    """Cartesian product of per-axis candidate tuples."""
    out: Tuple[Tuple[int, ...], ...] = ((),)
    for ax in axes:
        out = tuple(prefix + (c,) for prefix in out for c in ax)
    return out


# -- introspection / test hooks ---------------------------------------------


def pin(op: str, dims: Sequence[int], blocks: Sequence[int], *,
        dtype: str = "float32", kind: str = "") -> None:
    """Force a tile choice for one key (benchmarking what-ifs)."""
    key = TileKey(op, tuple(int(s) for s in dims), str(dtype), str(kind))
    _CACHE[key] = TileChoice(tuple(int(b) for b in blocks), "pinned")


def cache_info() -> Dict[TileKey, TileChoice]:
    """A snapshot of the resolution table (copy — mutations don't leak)."""
    return dict(_CACHE)


def clear_cache() -> None:
    _CACHE.clear()
