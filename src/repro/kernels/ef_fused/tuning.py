"""Per-platform kernel backend resolution + block autotuning (DESIGN.md §15).

The fused EF pipeline (§8) lowers through three Pallas backends:

* ``mosaic``    — compiled TPU lowering (sequential grid, revisited
  accumulators, in-kernel residual write: the 3-pass shape);
* ``triton``    — compiled GPU lowering (parallel grid: per-block
  partials + an order-preserving host-side fold, and a two-phase
  compact/residual split — no cross-program carried state, so the
  kernels are race-free on a real GPU);
* ``interpret`` — the Pallas interpreter running the mosaic kernels
  (the CPU backend of the tests).

``resolve_backend(None)`` picks the backend of the running platform —
mosaic on TPU, triton on GPU, the interpreter on CPU — and raises on any
other platform.  ``mosaic`` never runs interpreted: off a TPU it fails
to compile rather than emulate.  A ``use_backend(...)`` context or the
``REPRO_KERNEL_BACKEND`` env var overrides the default process-wide
(this is how the CI ``triton-interpret`` leg forces the GPU code path
through the interpreter on a CPU runner), and an explicit ``backend=``
kwarg always wins.  The legacy ``interpret=`` bool on the pipeline entry
points still works behind one :class:`DeprecationWarning`.

Block sizes are resolved per ``(backend, shape-class, dtype)`` as a
:class:`KernelConfig`:

1. an explicit kwarg at the call site wins;
2. else the checked-in table ``benchmarks/baselines/
   kernelconfig.<platform>.json`` is consulted (CI pins the chosen
   configs; steady-state steps pay zero autotune cost);
3. else the in-process cache;
4. else the deterministic heuristic.  A measured autotune over a small
   candidate grid (each candidate timed with ``block_until_ready``)
   runs only when asked for (``measure=True``: the table writer), never
   from inside a traced step.

Per-dtype block minima: TPU tiles are ``(sublanes, 128)`` lanes with
sublanes = 32 / itemsize (f32 → 8×128 = 1024, bf16 → 16×128 = 2048),
and Triton wants power-of-two columns sized so a block spans at least
one 4 KiB coalesced segment per warp (f32 → 1024, bf16 → 2048).  The
interpreter keeps the legacy 2048 floor for every dtype so CPU CI
numbers are unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import time
import warnings
from contextlib import contextmanager
from typing import Dict, Optional

BACKENDS = ("mosaic", "triton", "interpret")
ENV_BACKEND = "REPRO_KERNEL_BACKEND"
ENV_TABLE_DIR = "REPRO_KERNELCONFIG_DIR"
TABLE_SCHEMA = "kernelconfig/v1"

# interpreter-mode grid bounds (quadratic-cost guard — ops.py docstring)
MAX_INTERPRET_BLOCKS = 64
MAX_INTERPRET_STATS_BLOCKS = 4
INTERPRET_MIN_BLOCK = 2048

_PLATFORM_BACKEND = {"tpu": "mosaic", "gpu": "triton", "cuda": "triton",
                     "rocm": "triton", "cpu": "interpret"}
# platforms on which the triton lowering compiles; anywhere else it runs
# under the Pallas interpreter (the CI smoke path for the GPU lowering)
_COMPILES_ON = {"triton": ("gpu", "cuda", "rocm")}


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One resolved kernel configuration for the fused EF pipeline.

    ``block`` drives the compaction kernel, ``stats_block`` the
    reduction kernels (moments/hist/tree-count), ``bcap_slack`` the
    staging-width multiplier of ``fused_default_bcap``;
    ``num_warps``/``num_stages`` only reach the Triton lowering.
    ``source`` records provenance (``heuristic``/``table``/``autotune``)
    for logs and table audits.
    """
    backend: str
    block: int
    stats_block: int
    bcap_slack: float = 2.0
    num_warps: int = 4
    num_stages: int = 2
    source: str = "heuristic"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "KernelConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})


# ---------------------------------------------------------------------------
# backend resolution
# ---------------------------------------------------------------------------

_BACKEND_OVERRIDE: list = []      # use_backend() context stack
_INTERPRET_WARNED = False         # deprecation shim fires exactly once


def _platform() -> str:
    import jax
    return jax.default_backend()


def default_backend(platform: Optional[str] = None) -> str:
    """The kernel backend for ``platform``: the compiled lowering on a
    TPU or GPU, the interpreter on the CPU; any other platform raises."""
    platform = platform or _platform()
    try:
        return _PLATFORM_BACKEND[platform]
    except KeyError:
        raise ValueError(f"no kernel backend for platform {platform!r}; "
                         f"have {sorted(_PLATFORM_BACKEND)}") from None


@contextmanager
def use_backend(backend: str):
    """Force every ``backend=None`` resolution inside the context.

    This is the seam that carries a kernel-backend choice through call
    stacks that do not thread kernel kwargs (``dist/aggregate`` →
    ``segmented`` → ``ops``) — e.g. exercising the Triton lowering
    end-to-end through ``aggregate_bucketed``.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}; "
                         f"have {BACKENDS}")
    _BACKEND_OVERRIDE.append(backend)
    try:
        yield
    finally:
        _BACKEND_OVERRIDE.pop()


def _warn_interpret_kwarg() -> None:
    global _INTERPRET_WARNED
    if not _INTERPRET_WARNED:
        _INTERPRET_WARNED = True
        warnings.warn(
            "the interpret= kwarg of the fused EF pipeline is deprecated; "
            "pass backend='mosaic'|'triton'|'interpret' (or leave both "
            "unset to pick the compiled lowering for this platform)",
            DeprecationWarning, stacklevel=3)


def resolve_backend(backend: Optional[str] = None,
                    interpret: Optional[bool] = None,
                    platform: Optional[str] = None) -> str:
    """Three-way backend resolution (ISSUE 10 acceptance rules).

    Priority: explicit ``backend=`` > legacy ``interpret=`` bool (one
    ``DeprecationWarning`` per process) > :func:`use_backend` context >
    ``REPRO_KERNEL_BACKEND`` env > the platform's compiled lowering.
    """
    if backend is not None:
        if backend not in BACKENDS:
            raise ValueError(f"unknown kernel backend {backend!r}; "
                             f"have {BACKENDS}")
        return backend
    if interpret is not None:
        _warn_interpret_kwarg()
        return "interpret" if interpret else default_backend(platform)
    if _BACKEND_OVERRIDE:
        return _BACKEND_OVERRIDE[-1]
    env = os.environ.get(ENV_BACKEND, "")
    if env:
        if env not in BACKENDS:
            raise ValueError(f"{ENV_BACKEND}={env!r} is not one of "
                             f"{BACKENDS}")
        return env
    return default_backend(platform)


def compiler_params(backend: str, num_warps: int = 4, num_stages: int = 2):
    """Pallas compiler params for the kernel shape of ``backend``.

    The triton shape carries its warp configuration; the sequential
    shape (mosaic, and the interpreter running the same kernels) states
    its grid as ``arbitrary`` — a revisited accumulator or a carried
    scratch scalar orders every grid step after the previous one.  The
    interpreter ignores both.
    """
    if backend == "triton":
        from jax.experimental.pallas import triton as plgpu
        return plgpu.CompilerParams(num_warps=num_warps,
                                    num_stages=num_stages)
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def exec_interpret(backend: str, platform: Optional[str] = None) -> bool:
    """Whether ``backend`` runs under the Pallas interpreter here.

    ``interpret`` always does and ``mosaic`` never does: it compiles for
    the TPU or raises.  ``triton`` off a GPU keeps its kernel structure
    but executes interpreted (the CPU smoke leg of the GPU lowering).
    """
    if backend == "interpret":
        return True
    if backend == "mosaic":
        return False
    return (platform or _platform()) not in _COMPILES_ON[backend]


# ---------------------------------------------------------------------------
# TPU tile geometry shared by the sequential kernel shape
# ---------------------------------------------------------------------------

LANES = 128            # minor dim of every kernel operand view
SUBLANES = 8           # f32 sublanes: height of one (8, 128) vreg tile
GROUP = 8              # blocks per sequential grid step (at most)


def block_rows(block: int) -> int:
    """Rows of the ``(rows, LANES)`` view one ``block``-element block spans."""
    if block % LANES:
        raise ValueError(f"block {block} is not a multiple of {LANES} lanes")
    return block // LANES


def acc_rows(rows: int) -> int:
    """Height of the accumulator tile a ``rows``-row block folds into:
    one (8, 128) tile, or the whole block when it is shorter."""
    return SUBLANES if rows % SUBLANES == 0 else rows


def fold_tiles(x, sub: int, op):
    """Fold a ``(rows, LANES)`` value into one ``(sub, LANES)`` tile,
    sub-tile by sub-tile in row order.  Elementwise ops only, so every
    lowering combines the same elements in the same order."""
    acc = x[0:sub]
    for r in range(sub, x.shape[0], sub):
        acc = op(acc, x[r:r + sub])
    return acc


# ---------------------------------------------------------------------------
# per-(backend, dtype) block minima and the deterministic heuristic
# ---------------------------------------------------------------------------


def _itemsize(dtype) -> int:
    import jax.numpy as jnp
    return jnp.dtype(dtype).itemsize


def min_block(backend: str, dtype="float32") -> int:
    """Smallest legal block (lane count) for ``(backend, dtype)``.

    mosaic: one full TPU tile — ``(32 / itemsize)`` sublanes × 128
    lanes (f32 1024, bf16 2048, int8/fp8 4096).  triton: power-of-two
    columns, at least 4 KiB of operand per block (f32 1024, bf16 2048).
    interpret: the legacy 2048 floor regardless of dtype (keeps CPU CI
    behavior and the committed baselines unchanged).
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}; "
                         f"have {BACKENDS}")
    if backend == "interpret":
        return INTERPRET_MIN_BLOCK
    itemsize = max(1, min(4, _itemsize(dtype)))
    if backend == "mosaic":
        return (32 // itemsize) * 128
    return 4096 // itemsize          # triton: pow2 by construction


def bounded_block(d: int, max_blocks: int, base: int) -> int:
    """Smallest pow2 multiple of ``base`` with ``<= max_blocks`` blocks."""
    block = base
    while d > block * max_blocks:
        block *= 2
    return block


def choose_block(d: int, backend: str = "interpret",
                 dtype="float32") -> int:
    """Compaction-kernel block size for a ``d``-element leaf."""
    base = min_block(backend, dtype)
    if backend == "interpret":
        # interpreter charges O(d) per grid step -> bound the block count
        return bounded_block(d, MAX_INTERPRET_BLOCKS, base)
    return base


def choose_stats_block(d: int, backend: str = "interpret",
                       dtype="float32") -> int:
    """Block size for the reduction kernels (moments/hist/counts) —
    O(1)-per-element compute, tiny outputs: the interpreter wants the
    largest blocks possible; compiled backends take 4 tiles per grid
    step (bounded by the leaf's own pow2 envelope) so the grid stays
    short without starving parallelism."""
    base = min_block(backend, dtype)
    if backend == "interpret":
        return bounded_block(d, MAX_INTERPRET_STATS_BLOCKS, base)
    return max(base, min(4 * base, shape_class(d)))


def heuristic_config(backend: str, d: int, dtype="float32") -> KernelConfig:
    return KernelConfig(backend=backend,
                        block=choose_block(d, backend, dtype),
                        stats_block=choose_stats_block(d, backend, dtype),
                        source="heuristic")


# ---------------------------------------------------------------------------
# measured autotune + caches + checked-in table
# ---------------------------------------------------------------------------

_CACHE: Dict[str, KernelConfig] = {}


def shape_class(d: int) -> int:
    """pow2 ceiling of ``d`` — shapes in the same class share a config."""
    return max(1, 1 << (int(d) - 1).bit_length()) if d > 1 else 1


def _dtype_name(dtype) -> str:
    import jax.numpy as jnp
    return jnp.dtype(dtype).name


def config_key(backend: str, d: int, dtype) -> str:
    return f"{backend}/{_dtype_name(dtype)}/{shape_class(d)}"


def clear_cache() -> None:
    """Drop the in-process autotune cache (tests)."""
    _CACHE.clear()
    _load_table.cache_clear()


def table_dir() -> str:
    env = os.environ.get(ENV_TABLE_DIR, "")
    if env:
        return env
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.normpath(os.path.join(
        here, "..", "..", "..", "..", "benchmarks", "baselines"))


def table_path(platform: Optional[str] = None) -> str:
    return os.path.join(table_dir(),
                        f"kernelconfig.{platform or _platform()}.json")


@functools.lru_cache(maxsize=None)
def _load_table(path: str) -> tuple:
    """Checked-in ``{config_key: KernelConfig-dict}`` table (or empty)."""
    if not os.path.exists(path):
        return ()
    with open(path) as f:
        data = json.load(f)
    if data.get("schema") != TABLE_SCHEMA:
        raise ValueError(f"{path}: unexpected schema "
                         f"{data.get('schema')!r} (want {TABLE_SCHEMA!r})")
    return tuple(sorted(data.get("configs", {}).items()))


def candidates(backend: str, d: int, dtype="float32") -> list:
    """The measured-autotune candidate grid — deliberately small: a
    handful of block sizes within the leaf's pow2 envelope, and for
    Triton the two warp widths that matter at these block sizes."""
    base = min_block(backend, dtype)
    hi = max(base, shape_class(d))
    blocks = [b for b in (base, 2 * base, 4 * base, 8 * base) if b <= hi]
    out = []
    for block in blocks:
        stats = max(block, min(4 * block, hi))
        if backend == "triton":
            for warps in (4, 8):
                out.append(KernelConfig(backend, block, stats,
                                        num_warps=warps,
                                        source="autotune"))
        else:
            out.append(KernelConfig(backend, block, stats,
                                    source="autotune"))
    return out


def _time_config(cfg: KernelConfig, d: int, dtype, iters: int = 5) -> float:
    """Median wall seconds of one fused EF step under ``cfg`` (compiled
    dispatch, ``block_until_ready`` inside the timed region)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ef_fused.ops import fused_compress_ef

    k = max(1, d // 1000)
    g = (0.02 * jax.random.normal(jax.random.PRNGKey(0), (d,))
         ).astype(dtype)
    e = (0.01 * jax.random.normal(jax.random.PRNGKey(1), (d,))
         ).astype(jnp.float32)

    fn = jax.jit(lambda g, e: fused_compress_ef(
        g, e, "gaussiank", k, block=cfg.block, stats_block=cfg.stats_block,
        backend=cfg.backend, num_warps=cfg.num_warps,
        num_stages=cfg.num_stages))
    jax.block_until_ready(fn(g, e))              # compile outside the clock
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(g, e))
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def autotune_measure(backend: str, d: int, dtype="float32",
                     timer=None) -> KernelConfig:
    """Time the candidate grid once and return the winner."""
    timer = timer or _time_config
    cands = candidates(backend, d, dtype)
    timed = [(timer(c, d, dtype), i) for i, c in enumerate(cands)]
    best = min(timed)[1]
    return dataclasses.replace(cands[best], source="autotune")


def resolve_config(d: int, dtype="float32", *,
                   backend: Optional[str] = None,
                   interpret: Optional[bool] = None,
                   platform: Optional[str] = None,
                   measure: bool = False,
                   timer=None) -> KernelConfig:
    """The resolution ladder of the module docstring, cached per
    ``(backend, shape-class, dtype)``.

    Without ``measure`` the result is deterministic: the table, else the
    heuristic.  ``measure=True`` times the candidate grid instead (the
    table writer; tests inject a stub ``timer``) — never from inside a
    traced step, where timed candidates would pick the block geometry
    and with it the summation order.
    """
    backend = resolve_backend(backend, interpret, platform)
    key = config_key(backend, d, dtype)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    for tkey, tcfg in _load_table(table_path(platform)):
        if tkey == key:
            cfg = dataclasses.replace(KernelConfig.from_dict(tcfg),
                                      backend=backend, source="table")
            _CACHE[key] = cfg
            return cfg
    if measure:
        cfg = autotune_measure(backend, d, dtype, timer=timer)
    else:
        cfg = heuristic_config(backend, d, dtype)
    _CACHE[key] = cfg
    return cfg


# ---------------------------------------------------------------------------
# table writer (checked-in per-platform config pins)
# ---------------------------------------------------------------------------

TABLE_DS = (2 ** 12, 2 ** 16, 2 ** 20, 2 ** 22)
TABLE_DTYPES = ("float32", "bfloat16")


def write_table(path: Optional[str] = None, *, ds=TABLE_DS,
                dtypes=TABLE_DTYPES, backend: Optional[str] = None,
                measure: Optional[bool] = None) -> str:
    """Resolve (and, on a compiled backend, measure) the config for
    every ``(shape-class, dtype)`` cell and write the per-platform
    table ``_resolve`` consults first."""
    import jax

    from repro.launch.env import describe_env

    platform = jax.default_backend()
    backend = resolve_backend(backend, None, platform)
    if measure is None:
        # interpreter timings would only measure emulation overhead
        measure = not exec_interpret(backend, platform)
    configs = {}
    for dtype in dtypes:
        for d in ds:
            key = config_key(backend, d, dtype)
            if key in configs:
                continue
            cfg = resolve_config(d, dtype, backend=backend,
                                 measure=measure)
            configs[key] = cfg.to_dict()
    path = path or table_path(platform)
    data = {"schema": TABLE_SCHEMA, "platform": platform,
            "env": describe_env(), "configs": configs}
    with open(path, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="",
                    help="output path (default: the platform table under "
                         "benchmarks/baselines/)")
    ap.add_argument("--backend", default="",
                    help="kernel backend to tune (default: the platform's "
                         "compiled lowering)")
    ap.add_argument("--heuristic", action="store_true",
                    help="write the deterministic heuristic configs "
                         "instead of measuring")
    args = ap.parse_args(argv)
    path = write_table(args.out or None,
                       backend=args.backend or None,
                       measure=False if args.heuristic else None)
    print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
