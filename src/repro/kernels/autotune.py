"""Per-block-shape kernel autotune cache (the DBCSR ``libsmm_acc`` idea).

Nonuniform tilings hand the local engines a zoo of block shapes, and one
generic kernel choice (``jnp.matmul`` vs the tiled Pallas kernel vs the
block-sparse/grouped/factored routes) cannot win everywhere — DBCSR
(arXiv:1910.13555) ships a per-block-shape tuned kernel library for
exactly this reason.  This module is the runtime analogue:

* shapes are coarsened into **buckets** ``(bm, bk, bn, rank, dtype)``
  (power-of-two rounding), so one measurement covers a neighborhood;
* :meth:`KernelAutotuner.tune` benchmarks every applicable route on a
  representative problem of the bucket shape and records the winner and
  the per-route times;
* winners persist to JSON (:meth:`save` / :meth:`load`) the way
  ``serve.engine.warm_matmul_plans`` persists schedule choices, and the
  ``REPRO_AUTOTUNE_CACHE`` env var points the process singleton at a
  cache file;
* consumers (``core.summa._local_dot``, ``core.api.NonuniformMatmul``)
  only ever call :meth:`lookup` / :meth:`winner` — **lookup never
  benchmarks**, so consults inside jit tracing are free and an empty or
  disabled cache (``REPRO_AUTOTUNE=0``) leaves every execution path and
  executable-cache key bitwise identical to the pre-autotune behavior
  (:func:`cache_fingerprint` returns ``""`` exactly then).

Routes benchmarked per bucket:

``xla``
    ``jnp.matmul`` — the generic baseline; always a candidate, so a
    recorded winner is by construction never slower than the generic
    kernel on its own bucket (measured on the tuning machine).
``pallas``
    ``kernels.ops.tiled_matmul`` over a small tile sweep; the winning
    ``(bm, bk, bn)`` tile triple is recorded as ``tiles``.
``bsmm``
    the block-sparse kernel with a full mask — prices the CSR indirection
    so masked plans know when the structured kernel stops paying.
``grouped``
    the MegaBlocks-layout grouped GEMM with a single expert — the
    rank-sparse stage-1 shape (``kernels.ops.ranksparse_matmul``).
``factored``
    only when ``rank > 0``: the two-stage ``U @ (V @ B)`` skinny-gemm
    pipeline at the bucket's rank.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time

import numpy as np

__all__ = [
    "KernelAutotuner",
    "bucket_key",
    "autotune_cache",
    "set_autotune_cache",
    "cache_fingerprint",
    "autotune_enabled",
    "preferred_tile",
]

#: every route the tuner knows; ``factored`` only applies at rank > 0.
ROUTES = ("xla", "pallas", "bsmm", "grouped", "factored")

#: pallas tile sweep per bucket (clamped to the bucket shape).
TILE_CANDIDATES = (128, 256, 512)


def _pow2_bucket(x: int, lo: int = 8, hi: int = 4096) -> int:
    """Round up to the next power of two, clamped to [lo, hi]."""
    x = int(max(x, 1))
    b = 1 << (x - 1).bit_length()
    return int(min(max(b, lo), hi))


def bucket_key(
    m: int, k: int, n: int, *, rank: int = 0, dtype="float32"
) -> tuple:
    """Coarsen a local-gemm shape into its autotune bucket.

    ``rank=0`` means dense (no factored structure); positive ranks bucket
    to powers of two with a floor of 8 so nearby ranks share entries.
    """
    rb = _pow2_bucket(rank, lo=8, hi=1024) if rank > 0 else 0
    return (
        _pow2_bucket(m),
        _pow2_bucket(k),
        _pow2_bucket(n),
        rb,
        str(np.dtype(dtype)),
    )


def _key_str(key: tuple) -> str:
    m, k, n, r, dt = key
    return f"{m}x{k}x{n}xr{r}x{dt}"


def _key_parse(s: str) -> tuple:
    m, k, n, r, dt = s.split("x", 4)
    return (int(m), int(k), int(n), int(r[1:]), dt)


def autotune_enabled() -> bool:
    """``REPRO_AUTOTUNE=0`` disables every consult (bitwise-off switch)."""
    return os.environ.get("REPRO_AUTOTUNE", "1") != "0"


def _time_call(fn, *args, repeats: int = 3) -> float:
    """Best-of-``repeats`` wall time of ``fn(*args)`` (post-compile)."""
    import jax

    jax.block_until_ready(fn(*args))  # compile outside the timed region
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


@dataclasses.dataclass
class KernelAutotuner:
    """Bucketed route winners; see the module docstring for semantics."""

    table: dict = dataclasses.field(default_factory=dict)

    # -- consult (lookup-only: safe inside jit tracing) ----------------------

    def lookup(
        self, m: int, k: int, n: int, *, rank: int = 0, dtype="float32"
    ) -> dict | None:
        """The bucket's entry, or ``None`` (miss / disabled). Never tunes."""
        if not autotune_enabled():
            return None
        return self.table.get(bucket_key(m, k, n, rank=rank, dtype=dtype))

    def winner(
        self, m: int, k: int, n: int, *, rank: int = 0, dtype="float32"
    ) -> str | None:
        entry = self.lookup(m, k, n, rank=rank, dtype=dtype)
        return entry["winner"] if entry else None

    def fingerprint(self) -> str:
        """Content digest of the table; ``""`` when empty or disabled.

        Consumers append a non-empty fingerprint to their executable
        cache keys, so flipping the cache never aliases two different
        traced programs — and an empty/disabled cache leaves the keys
        (and therefore plan-digest behavior) bitwise unchanged.
        """
        if not autotune_enabled() or not self.table:
            return ""
        h = hashlib.sha1()
        for k in sorted(self.table, key=_key_str):
            e = self.table[k]
            h.update(_key_str(k).encode())
            h.update(str(e.get("winner")).encode())
            h.update(str(e.get("tiles")).encode())
        return h.hexdigest()[:16]

    # -- tuning (benchmarks: never call inside tracing) ----------------------

    def _routes(self, key: tuple):
        """Build ``{route: (callable, args)}`` for a bucket; jit-wrapped.

        Only routes that apply to the bucket are built (``factored`` needs
        a rank), and every block they launch tiles on TPU, so a route
        that fails to compile or run is an error, never a silent skip.
        The ``pallas`` route is parameterized by its tile triple, so it is
        returned as ``(tiles -> callable, args)`` and swept by ``tune``.
        """
        import jax
        import jax.numpy as jnp

        from repro.kernels import ops as kops

        bm, bk, bn, rb, dt = key
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.standard_normal((bm, bk)), dtype=dt)
        b = jnp.asarray(rng.standard_normal((bk, bn)), dtype=dt)
        routes = {"xla": (jax.jit(jnp.matmul), (a, b))}

        def pallas_fn(tiles):
            return jax.jit(
                lambda x, y, _t=tiles: kops.tiled_matmul(
                    x, y, bm=_t[0], bk=_t[1], bn=_t[2]
                )
            )

        routes["pallas"] = (pallas_fn, (a, b))

        # 128-wide blocks (or the whole bucket dim below that) tile on TPU
        mask = np.ones((bm // min(bm, 128), bk // min(bk, 128)), dtype=bool)
        routes["bsmm"] = (
            jax.jit(lambda x, y: kops.bsmm(x, y, mask)), (a, b)
        )

        bt = min(bm, 256)  # bm is a power of two, so bt divides it
        te = jnp.zeros((bm // bt,), jnp.int32)
        routes["grouped"] = (
            jax.jit(
                lambda x, y: kops.grouped_gemm(x, y[None], te, bt=bt)
            ),
            (a, b),
        )

        if rb > 0:
            u = jnp.asarray(rng.standard_normal((bm, rb)), dtype=dt)
            v = jnp.asarray(rng.standard_normal((rb, bk)), dtype=dt)
            routes["factored"] = (
                jax.jit(lambda uu, vv, y: uu @ (vv @ y)), (u, v, b)
            )
        return routes

    def tune(
        self,
        m: int,
        k: int,
        n: int,
        *,
        rank: int = 0,
        dtype="float32",
        repeats: int = 3,
        routes: tuple[str, ...] | None = None,
    ) -> dict:
        """Benchmark the routes on this shape's bucket and record the winner.

        Idempotent per bucket (re-tuning overwrites).  ``routes`` limits
        the sweep (e.g. ``("xla", "pallas")`` on hosts where the
        interpret-mode structured kernels are too slow to time).
        Returns the entry: ``{"winner", "times_s", "tiles"}``.
        """
        key = bucket_key(m, k, n, rank=rank, dtype=dtype)
        bm, bk, bn = key[:3]
        built = self._routes(key)
        times: dict[str, float] = {}
        tiles = None
        for name, (fn, args) in built.items():
            if routes is not None and name not in routes:
                continue
            if name == "pallas":
                best_t = float("inf")
                for t in TILE_CANDIDATES:
                    cand = (min(t, bm), min(t, bk), min(t, bn))
                    tt = _time_call(fn(cand), *args, repeats=repeats)
                    if tt < best_t:
                        best_t, tiles = tt, cand
                    if cand == (bm, bk, bn):
                        break  # larger candidates clamp to the same tiling
                times[name] = best_t
            else:
                times[name] = _time_call(fn, *args, repeats=repeats)
        if not times:
            raise ValueError(f"no requested route applies to bucket {key}")
        winner = min(times, key=times.get)
        entry = {
            "winner": winner,
            "times_s": {r: float(t) for r, t in times.items()},
            "tiles": list(tiles) if tiles else None,
        }
        self.table[key] = entry
        return entry

    # -- persistence (the ``warm_matmul_plans`` analogue) --------------------

    def save(self, path: str) -> None:
        data = {
            "version": 1,
            "entries": {_key_str(k): v for k, v in self.table.items()},
        }
        with open(path, "w") as f:
            json.dump(data, f, indent=2, sort_keys=True)

    def load(self, path: str, *, merge: bool = True) -> int:
        """Load entries from ``path``; returns how many were installed.

        ``merge=True`` (default) keeps existing in-memory entries on key
        collisions losing to the file — the file is the persisted truth.
        """
        with open(path) as f:
            data = json.load(f)
        entries = data.get("entries", {})
        if not merge:
            self.table.clear()
        for ks, e in entries.items():
            self.table[_key_parse(ks)] = e
        return len(entries)


_CACHE: KernelAutotuner | None = None


def autotune_cache() -> KernelAutotuner:
    """The process singleton; seeded from ``REPRO_AUTOTUNE_CACHE`` if the
    env var names an existing JSON file (the CI warm-restore path)."""
    global _CACHE
    if _CACHE is None:
        _CACHE = KernelAutotuner()
        path = os.environ.get("REPRO_AUTOTUNE_CACHE", "")
        if path and os.path.exists(path):
            _CACHE.load(path)
    return _CACHE


def set_autotune_cache(cache: KernelAutotuner | None) -> None:
    """Swap the process singleton (tests; ``None`` resets to empty-lazy)."""
    global _CACHE
    _CACHE = cache


def cache_fingerprint() -> str:
    """Singleton fingerprint without forcing env-file loading semantics on
    callers; ``""`` when the cache is empty or disabled."""
    return autotune_cache().fingerprint()


def preferred_tile(
    max_block: int, *, dtype="float32", candidates=TILE_CANDIDATES
) -> int | None:
    """Physical tile choice for ``NonuniformMatmul`` bucketing.

    Scans square ``(c, c, c)`` buckets the cache has measured and returns
    the candidate whose winning route is fastest, ``None`` on a cold
    cache (caller falls back to its static default).  ``max_block`` caps
    the tile at the largest logical block so bucketization stays exact.
    """
    cache = autotune_cache()
    best_c, best_t = None, float("inf")
    for c in candidates:
        if c > _pow2_bucket(max_block, lo=8):
            continue
        entry = cache.lookup(c, c, c, dtype=dtype)
        if not entry:
            continue
        t = entry["times_s"][entry["winner"]]
        # normalize by the bucket's flops so sizes are comparable
        t_norm = t / float(c) ** 3
        if t_norm < best_t:
            best_c, best_t = c, t_norm
    return best_c
