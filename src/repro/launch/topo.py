"""Topology descriptors for the wire cost model (DESIGN.md SS14).

The roofline/overlap machinery used to price every machine with three
module-level constants (``PEAK_FLOPS``/``HBM_BW``/``LINK_BW``) and a
bandwidth-only wire term.  This module replaces those with explicit,
serialisable descriptors:

* :class:`HardwareSpec` — per-chip compute model: peak FLOP/s and HBM
  bandwidth.  Every roofline/benchmark record now names the spec that
  priced it instead of silently assuming a TPU.
* :class:`LinkSpec` — an alpha-beta link model: ``alpha_s`` is the
  per-message (per-collective-dispatch) latency in seconds, ``beta_Bps``
  the sustained bandwidth in bytes/s.  Wire time for a transfer of
  ``n`` messages totalling ``B`` bytes is ``n * alpha + B / beta``.
* :class:`Topology` — a :class:`HardwareSpec` plus one :class:`LinkSpec`
  per mesh axis (with a default for unlisted axes).  Loadable from a
  JSON descriptor (``--topology topo.json``) or filled in by
  :func:`measure_topology`, a startup ping/ramp microbenchmark over the
  live mesh axes.

JSON schema (all link fields in SI units — seconds, bytes/s)::

    {
      "name": "my-cluster",
      "hardware": {"name": "tpu-v5e", "peak_flops": 1.97e14,
                   "hbm_bw": 8.19e11},
      "links": {
        "pod":  {"alpha_s": 1.0e-4, "beta_Bps": 1.0e9},
        "data": {"alpha_s": 1.0e-6, "beta_Bps": 5.0e10}
      },
      "default_link": {"alpha_s": 1.0e-6, "beta_Bps": 5.0e10}
    }

Only the stdlib is imported at module scope; jax is pulled in lazily by
the ``measure_*`` microbenchmarks so the descriptor types stay cheap to
import from tools/ and benchmarks/.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

__all__ = [
    "HardwareSpec", "LinkSpec", "Topology",
    "DEFAULT_HW", "DEFAULT_LINK", "DEFAULT_TOPOLOGY",
    "load_topology", "save_topology",
    "measure_hardware", "measure_topology",
]


@dataclass(frozen=True)
class HardwareSpec:
    """Per-chip compute model used to price roofline terms.

    Defaults match the former ``roofline.PEAK_FLOPS``/``HBM_BW``
    module globals (TPU-v5e-flavoured bf16 numbers), so existing
    call sites price identically unless they pass a spec.
    """
    name: str = "tpu-v5e"
    peak_flops: float = 197e12   # FLOP/s (bf16)
    hbm_bw: float = 819e9        # bytes/s

    def to_dict(self) -> dict:
        return {"name": self.name, "peak_flops": self.peak_flops,
                "hbm_bw": self.hbm_bw}

    @classmethod
    def from_dict(cls, d: dict) -> "HardwareSpec":
        return cls(name=str(d.get("name", "unnamed")),
                   peak_flops=float(d["peak_flops"]),
                   hbm_bw=float(d["hbm_bw"]))


@dataclass(frozen=True)
class LinkSpec:
    """alpha-beta model of one mesh-axis interconnect.

    ``alpha_s`` is charged once per message (one collective dispatch
    moves one array — a codec pair is two messages); ``beta_Bps`` is
    the sustained point-to-point bandwidth.  The default bandwidth
    matches the former ``roofline.LINK_BW`` global; the default alpha
    is a typical intra-pod ICI dispatch latency.
    """
    alpha_s: float = 1e-6        # seconds per message
    beta_Bps: float = 50e9       # bytes per second

    def time_s(self, n_messages: float, nbytes: float) -> float:
        """Wire seconds for ``n_messages`` totalling ``nbytes``."""
        return n_messages * self.alpha_s + nbytes / self.beta_Bps

    def to_dict(self) -> dict:
        return {"alpha_s": self.alpha_s, "beta_Bps": self.beta_Bps}

    @classmethod
    def from_dict(cls, d: dict) -> "LinkSpec":
        return cls(alpha_s=float(d["alpha_s"]),
                   beta_Bps=float(d["beta_Bps"]))


DEFAULT_HW = HardwareSpec()
DEFAULT_LINK = LinkSpec()


@dataclass(frozen=True)
class Topology:
    """A hardware spec plus one link spec per mesh axis.

    ``links`` is stored as a tuple of ``(axis_name, LinkSpec)`` pairs so
    the descriptor stays hashable (it rides inside jitted-function
    closures via the tuner).  Unlisted axes fall back to
    ``default_link``.
    """
    hardware: HardwareSpec = DEFAULT_HW
    links: Tuple[Tuple[str, LinkSpec], ...] = ()
    default_link: LinkSpec = DEFAULT_LINK
    name: str = "default"

    def link(self, axis: str) -> LinkSpec:
        for ax, spec in self.links:
            if ax == axis:
                return spec
        return self.default_link

    def link_map(self) -> Dict[str, LinkSpec]:
        return dict(self.links)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "hardware": self.hardware.to_dict(),
            "links": {ax: spec.to_dict() for ax, spec in self.links},
            "default_link": self.default_link.to_dict(),
        }

    @classmethod
    def from_dict(cls, d: dict) -> "Topology":
        hw = (HardwareSpec.from_dict(d["hardware"])
              if "hardware" in d else DEFAULT_HW)
        default = (LinkSpec.from_dict(d["default_link"])
                   if "default_link" in d else DEFAULT_LINK)
        links = tuple(sorted(
            (ax, LinkSpec.from_dict(spec))
            for ax, spec in d.get("links", {}).items()))
        return cls(hardware=hw, links=links, default_link=default,
                   name=str(d.get("name", "unnamed")))


DEFAULT_TOPOLOGY = Topology()


def load_topology(path: str) -> Topology:
    """Parse a JSON topology descriptor (schema in the module docstring)."""
    with open(path) as f:
        d = json.load(f)
    if not isinstance(d, dict):
        raise ValueError(f"{path}: topology descriptor must be a JSON object")
    return Topology.from_dict(d)


def save_topology(topo: Topology, path: str) -> None:
    with open(path, "w") as f:
        json.dump(topo.to_dict(), f, indent=2, sort_keys=True)
        f.write("\n")


# ---------------------------------------------------------------------------
# Startup microbenchmarks (ping/ramp).  jax imported lazily.
# ---------------------------------------------------------------------------

def _best_of(fn, reps: int) -> float:
    """Min wall-clock of ``fn()`` over ``reps`` timed runs (post-warmup)."""
    import time
    fn()                                  # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def measure_hardware(reps: int = 3, n: int = 1024,
                     copy_mb: int = 32) -> HardwareSpec:
    """Measure peak FLOP/s (f32 matmul) and memory bandwidth (big copy)
    of whatever backend jax is running on.  Deliberately crude — the
    point is that a CPU run prices itself as a CPU, not as a TPU."""
    import jax
    import jax.numpy as jnp

    a = jnp.ones((n, n), jnp.float32)
    mm = jax.jit(lambda x: x @ x)
    t_mm = _best_of(lambda: mm(a).block_until_ready(), reps)
    peak = 2.0 * n ** 3 / max(t_mm, 1e-9)

    words = copy_mb * (1 << 20) // 4
    buf = jnp.ones((words,), jnp.float32)
    cp = jax.jit(lambda x: x + 1.0)
    t_cp = _best_of(lambda: cp(buf).block_until_ready(), reps)
    hbm = 2.0 * words * 4 / max(t_cp, 1e-9)   # read + write

    return HardwareSpec(name=f"measured-{jax.devices()[0].platform}",
                        peak_flops=peak, hbm_bw=hbm)


def _axis_ring_time(mesh, axis: str, nbytes: int, rounds: int,
                    reps: int) -> float:
    """Seconds per ppermute round of ``nbytes`` along ``axis``:
    ``rounds`` chained ring shifts inside one jitted program (separated
    by optimization barriers so XLA cannot coalesce them), minus the
    same program with zero rounds (jit dispatch + copy overhead),
    divided out.  The subtraction matters: per-call overhead is easily
    10x a single round, and folding it into alpha would price every
    in-program collective as if it paid a fresh python dispatch."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P


    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    if n < 2:
        return 0.0
    words = max(1, nbytes // 4)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body_rounds(r):
        def body(x):
            for _ in range(r):
                x = jax.lax.ppermute(x, axis, perm)
                (x,) = jax.lax.optimization_barrier((x,))
            return x * 1.0
        return body

    x = jnp.ones((words,), jnp.float32)
    times = []
    for r in (0, rounds):
        fn = jax.jit(jax.shard_map(
            body_rounds(r), mesh=mesh, in_specs=P(), out_specs=P(),
            axis_names=set(mesh.axis_names), check_vma=False))
        times.append(_best_of(lambda: fn(x).block_until_ready(), reps))
    return max(0.0, times[1] - times[0]) / rounds


def measure_topology(mesh, *, small_bytes: int = 1 << 12,
                     large_bytes: int = 1 << 22, rounds: int = 8,
                     reps: int = 3,
                     hardware: Optional[HardwareSpec] = None) -> Topology:
    """Ping/ramp microbenchmark over the live mesh's data axes.

    For each data axis, times a small (``small_bytes``, latency-
    dominated ping) and a large (``large_bytes``, bandwidth-dominated
    ramp) ppermute round and solves the alpha-beta model::

        t(S) = alpha + S/beta ;  t(L) = alpha + L/beta
        beta = (L - S) / (t_L - t_S) ;  alpha = t_S - S/beta

    Axes of size 1 (and the model axis) keep :data:`DEFAULT_LINK`.
    """
    from repro.launch.mesh import data_axes_of

    hw = measure_hardware(reps=reps) if hardware is None else hardware
    links = []
    for axis in data_axes_of(mesh):
        t_s = _axis_ring_time(mesh, axis, small_bytes, rounds, reps)
        t_l = _axis_ring_time(mesh, axis, large_bytes, rounds, reps)
        if t_l <= t_s:
            # degenerate timing (noise swamped the ramp): keep the default
            links.append((axis, DEFAULT_LINK))
            continue
        beta = (large_bytes - small_bytes) / (t_l - t_s)
        alpha = max(0.0, t_s - small_bytes / beta)
        links.append((axis, LinkSpec(alpha_s=alpha, beta_Bps=beta)))
    return Topology(hardware=hw, links=tuple(sorted(links)),
                    name="measured")
