"""Kernel autotuner: grid-search tile/block parameters per ``SystemProfile``.

The Pallas/ref kernels behind ``kernels.ops`` run with the block sizes
they are given, or their own defaults (flash ``(block_q, block_kv)``,
dense decode's split-KV tile ``block_kv``, the SSD scan ``chunk``). This
module times every candidate parameter set on the machine it runs on
(the measure -> fit half of the DynamoLLM recipe, arXiv 2407.04014),
persists the winners, and lets
``core.pricing.TableOracle.from_autotune`` rebuild oracle phase grids from
the tuned timings. Dispatch never reads a cache: a caller that wants a
winner passes it as the kernel's kwarg (``AutotuneCache.resolve``).

Caches are versioned JSON under ``experiments/autotune/``, keyed by
``(kernel, backend, shape-bucket)`` per ``(profile, backend)`` file, and
stamped with the ``launch.envcfg`` environment fingerprint — a cache
recorded under a different environment refuses to load (``StaleCacheError``).

The timing callable defaults to ``benchmarks.microbench.time_kernel`` (the
generalized single-cell timer behind ``kernel_phase_samples``); tests inject
deterministic fake timers.
"""
from __future__ import annotations

import itertools
import json
import math
import os
from dataclasses import asdict, dataclass, field
from typing import (TYPE_CHECKING, Callable, Dict, Iterable, List, Mapping,
                    Optional, Sequence, Tuple)

from repro.kernels import ops
from repro.launch import envcfg

if TYPE_CHECKING:
    from repro.core.pricing import KernelSample

CACHE_VERSION = 1

# Default cache root (repo's experiments/ tree); benchmarks and tests may
# point elsewhere.
CACHE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "experiments", "autotune")

# Kernels the tuner knows.
KERNELS = ("flash_attention", "decode_attention", "ssm_scan")


class StaleCacheError(ValueError):
    """Cache recorded under a different environment fingerprint."""


# ------------------------------------------------------------- param spaces
# The kernels' own defaults per (kernel, backend), what ops dispatch runs
# with no kwarg — pinned bit-for-bit by tests/test_autotune.py. An empty
# dict means the kernel has no tunable parameters on that backend.
_PALLAS = ("pallas", "pallas_interpret")

DEFAULT_PARAMS: Dict[Tuple[str, str], Dict[str, object]] = {
    ("flash_attention", "ref"): {"block_q": 1024},
    **{("flash_attention", b): {"block_q": 128, "block_kv": 128}
       for b in _PALLAS},
    ("decode_attention", "ref"): {},
    **{("decode_attention", b): {"block_kv": 128} for b in _PALLAS},
    **{("ssm_scan", b): {"chunk": 128} for b in ("ref",) + _PALLAS},
}

# Candidate grids. Every space includes the default point, so the winner is
# never slower than the default on the measured grid (asserted in tests and
# by the autotune_sweep no-regression gate).
_SPACES: Dict[Tuple[str, str], Dict[str, Sequence[object]]] = {
    ("flash_attention", "ref"): {"block_q": (128, 256, 512, 1024, 2048)},
    **{("flash_attention", b): {"block_q": (64, 128, 256),
                                "block_kv": (64, 128, 256)} for b in _PALLAS},
    ("decode_attention", "ref"): {},
    **{("decode_attention", b): {"block_kv": (64, 128, 256, 512)}
       for b in _PALLAS},
    **{("ssm_scan", b): {"chunk": (16, 32, 64, 128, 256)}
       for b in ("ref",) + _PALLAS},
}


def default_params(kernel: str, backend: str) -> Dict[str, object]:
    """The kernel's own default parameters for (kernel, backend)."""
    try:
        return dict(DEFAULT_PARAMS[(kernel, backend)])
    except KeyError:
        raise KeyError(f"unknown kernel/backend {(kernel, backend)!r}") from None


def param_space(kernel: str, backend: str) -> List[Dict[str, object]]:
    """Cartesian candidate grid (default point first)."""
    space = _SPACES.get((kernel, backend))
    if space is None:
        raise KeyError(f"unknown kernel/backend {(kernel, backend)!r}")
    if not space:
        return []
    names = sorted(space)
    combos = [dict(zip(names, vals))
              for vals in itertools.product(*(space[k] for k in names))]
    default = default_params(kernel, backend)
    combos.sort(key=lambda c: c != default)        # default first
    return combos


# ----------------------------------------------------------- shape buckets
def _pow2(x: int) -> int:
    return 1 << max(0, math.ceil(math.log2(max(1, int(x)))))


def shape_bucket(kernel: str, **dims: int) -> str:
    """Canonical bucket key for a kernel invocation's shape.

    Sequence/context lengths and batch are bucketed to the next power of
    two: the tuned choice is driven by the padded grid the kernel actually
    runs, which is pow2-block granular. Head counts / head_dim are left out
    — they scale all candidates alike on these kernels.

      flash_attention    s=<seq>          -> "s1024"
      decode_attention   b=<batch> c=<ctx>-> "b8c2048"
      ssm_scan           s=<seq>          -> "s512"
    """
    if kernel in ("flash_attention", "ssm_scan"):
        return f"s{_pow2(dims['s'])}"
    if kernel == "decode_attention":
        return f"b{_pow2(dims['b'])}c{_pow2(dims['c'])}"
    raise KeyError(f"unknown kernel {kernel!r}")


# ------------------------------------------------------------------- cache
@dataclass(frozen=True)
class TunedEntry:
    """Winner of one (kernel, backend, shape-bucket) grid search.

    Carries the analytic work counts (flops/bytes/ctx) of the timed shape so
    ``TableOracle.from_autotune`` can refit pricing constants from the tuned
    timings without re-measuring.
    """
    kernel: str
    backend: str
    bucket: str
    params: Dict[str, object]          # winning parameters
    t_s: float                         # winner best-of-k seconds
    t_default_s: float                 # default params, same sweep
    noise_frac: float                  # winner's (median-best)/best spread
    flops: float
    bytes: float
    ctx: float
    shape: Dict[str, int] = field(default_factory=dict)

    @property
    def speedup(self) -> float:
        return self.t_default_s / self.t_s

    def key(self) -> str:
        return cache_key(self.kernel, self.backend, self.bucket)


def cache_key(kernel: str, backend: str, bucket: str) -> str:
    return f"{kernel}/{backend}/{bucket}"


class AutotuneCache:
    """Tuned winners for one (profile, backend), stamped with the recording
    environment fingerprint."""

    def __init__(self, profile: str, backend: str, *,
                 env: Optional[Dict[str, str]] = None,
                 entries: Iterable[TunedEntry] = ()):
        self.profile = profile
        self.backend = backend
        self.env = dict(env) if env is not None else envcfg.env_fingerprint()
        self.entries: Dict[str, TunedEntry] = {e.key(): e for e in entries}

    # ------------------------------------------------------------- queries
    def add(self, entry: TunedEntry) -> None:
        self.entries[entry.key()] = entry

    def resolve(self, kernel: str, backend: str,
                bucket: str) -> Optional[Dict[str, object]]:
        """Winning params for (kernel, backend, bucket), or None."""
        e = self.entries.get(cache_key(kernel, backend, bucket))
        return dict(e.params) if e is not None else None

    def tuned_samples(self) -> List["KernelSample"]:
        """The winners as ``KernelSample``s — the feed for
        ``fit_calibration`` / ``TableOracle.from_autotune``."""
        from repro.core.pricing import KernelSample
        return [KernelSample(e.kernel, e.flops, e.bytes, e.ctx, e.t_s,
                             noise_frac=e.noise_frac)
                for e in sorted(self.entries.values(), key=lambda e: e.key())]

    def geomean_speedup(self) -> float:
        """Geometric-mean tuned-vs-default speedup across entries."""
        ups = [e.speedup for e in self.entries.values()]
        if not ups:
            return 1.0
        return math.exp(sum(math.log(u) for u in ups) / len(ups))

    # ----------------------------------------------------------- artifacts
    def to_json(self) -> Dict[str, object]:
        return {
            "version": CACHE_VERSION,
            "profile": self.profile,
            "backend": self.backend,
            "env": self.env,
            "env_digest": envcfg.fingerprint_digest(self.env),
            "entries": {k: asdict(e) for k, e in sorted(self.entries.items())},
        }

    def dump(self, path: str) -> str:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=2, sort_keys=True)
        return path

    @classmethod
    def from_json(cls, data: Mapping[str, object], *,
                  require_env: bool = True) -> "AutotuneCache":
        version = data.get("version")
        if version != CACHE_VERSION:
            raise ValueError(f"autotune cache version {version!r} != "
                             f"supported {CACHE_VERSION}")
        env = dict(data["env"])                                # type: ignore[arg-type]
        recorded = data.get("env_digest")
        if recorded != envcfg.fingerprint_digest(env):
            raise ValueError("autotune cache corrupt: env_digest does not "
                             "match its recorded fingerprint")
        if require_env:
            current = envcfg.fingerprint_digest()
            if recorded != current:
                raise StaleCacheError(
                    f"autotune cache recorded under env {recorded} but the "
                    f"current env is {current}; re-run the autotuner "
                    "(or pass require_env=False to inspect it anyway)")
        entries = [TunedEntry(**e) for e in data["entries"].values()]  # type: ignore[union-attr]
        return cls(str(data["profile"]), str(data["backend"]), env=env,
                   entries=entries)

    @classmethod
    def load(cls, path: str, *, require_env: bool = True) -> "AutotuneCache":
        with open(path) as f:
            data = json.load(f)
        return cls.from_json(data, require_env=require_env)

    def __repr__(self) -> str:
        return (f"AutotuneCache(profile={self.profile!r}, "
                f"backend={self.backend!r}, entries={len(self.entries)})")


def cache_path(profile: str, backend: str, root: Optional[str] = None) -> str:
    """Canonical on-disk location for a (profile, backend) cache."""
    return os.path.join(root if root is not None else CACHE_DIR,
                        f"{profile}__{backend}.json")


# -------------------------------------------------------------- the tuner
# timer(kernel, shape, params, backend, iters, seed) -> KernelSample
Timer = Callable[..., "KernelSample"]

# Representative shapes per kernel (bucket-defining dims only; the timer
# fills in heads/head_dim). One entry per bucket the serving stack hits.
DEFAULT_SHAPES: Dict[str, Tuple[Dict[str, int], ...]] = {
    "flash_attention": ({"s": 1024}, {"s": 2048}),
    "decode_attention": ({"b": 8, "c": 1024}, {"b": 8, "c": 4096}),
    "ssm_scan": ({"s": 512}, {"s": 1024}),
}


def _default_timer() -> Timer:
    # lazy: benchmarks/ is a script dir, not part of the installed package
    try:
        from benchmarks.microbench import time_kernel
    except ImportError:                  # standalone: benchmarks/ on sys.path
        from microbench import time_kernel
    return time_kernel


def autotune(shapes: Optional[Mapping[str, Sequence[Dict[str, int]]]] = None,
             *, profile: str, backend: Optional[str] = None,
             iters: int = 5, seed: int = 0, timer: Optional[Timer] = None,
             verbose: bool = False) -> AutotuneCache:
    """Grid-search every (kernel, shape) cell and return the winners.

    ``backend`` defaults to the resolved auto backend (compiled Pallas on
    TPU, the jnp path elsewhere) so the tuner measures what serving will
    run. Each cell times every candidate in ``param_space`` best-of-k
    (warmup excluded) and keeps the fastest; the default parameters are in
    every space, so a winner is never slower than the default *on the
    measured grid* by construction.
    """
    if backend is None:
        backend = ops.resolve_backend("auto")
    if timer is None:
        timer = _default_timer()
    if shapes is None:
        shapes = DEFAULT_SHAPES

    cache = AutotuneCache(profile, backend)
    for kernel in sorted(shapes):
        if kernel not in KERNELS:
            raise KeyError(f"unknown kernel {kernel!r}; expected one of "
                           f"{KERNELS}")
        candidates = param_space(kernel, backend)
        if not candidates:
            continue                                 # nothing tunable here
        for shape in shapes[kernel]:
            bucket = shape_bucket(kernel, **shape)
            best = None                              # (t_s, params, sample)
            t_default = None
            default = default_params(kernel, backend)
            for params in candidates:
                sample = timer(kernel, dict(shape), params=params,
                               backend=backend, iters=iters, seed=seed)
                if params == default:
                    t_default = sample.t_s
                if best is None or sample.t_s < best[0]:
                    best = (sample.t_s, params, sample)
                if verbose:
                    print(f"[autotune] {kernel}/{bucket} {params} "
                          f"-> {sample.t_s * 1e3:.3f} ms")
            assert best is not None and t_default is not None
            t_s, params, sample = best
            cache.add(TunedEntry(
                kernel=kernel, backend=backend, bucket=bucket,
                params=dict(params), t_s=t_s, t_default_s=t_default,
                noise_frac=float(getattr(sample, "noise_frac", 0.0)),
                flops=sample.flops, bytes=sample.bytes, ctx=sample.ctx,
                shape=dict(shape)))
            if verbose:
                print(f"[autotune] {kernel}/{bucket} winner {params} "
                      f"({t_default / t_s:.2f}x vs default)")
    return cache
