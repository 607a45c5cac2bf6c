"""Block-size autotuning for the Pallas kernels.

The kernels expose their schedule knobs (``block_q``/``block_k``/
``block_m``/``block_n``/``block_c``/``block_f``) as static kwargs with
conservative defaults (``lora_matmul``'s are derived from the call's
shape, and its sweep spans the sizes that rule picks). This module
sweeps those knobs per (kernel, shape) pair, times real compiled calls
with warm-up excluded, and persists the winners to a
**platform-keyed** JSON tuning cache that ``dispatch.get_kernel``
consults at kernel resolution — so a tuned TPU run picks up its block
sizes with no call-site changes, while CPU / interpret behavior is
untouched (cache misses fall back to the defaults).

Design rules (DESIGN.md §14):

* **Lint-valid by construction** — candidate configs are materialized
  through each kernel's declared ``*_layout()`` adapter
  (``dispatch.kernel_layouts()``) and any candidate the L003 layout
  lint rejects is dropped before timing. Oversize candidates collapse
  onto smaller ones via ``tile_block_cap``; duplicates (same derived
  ``BlockLayout``) are timed once.
* **Never slower than default** — the default config is always timed
  first and a candidate replaces it only on a *strict* improvement, so
  ties and noise resolve to the default blocks.
* **Pipeline depth rides the innermost block** — the number of
  pipelined grid steps is ``padded_dim / innermost_block``, so sweeping
  the innermost block size sweeps the software-pipeline depth; there is
  no separate knob to tune.
* **Stale entries invalidate** — each kernel's cache bucket records the
  ``*_layout()`` adapter signature; a signature change (new/renamed
  knob) drops every entry for that kernel.

``ssd_scan`` is deliberately NOT tunable: its ``chunk`` knob changes
the chunked recurrence's floating-point grouping (numerics), not just
the schedule — retuning it would drift the golden round logs.

CLI::

    PYTHONPATH=src python -m repro.kernels.autotune \
        [--kernels lora_matmul,flash_decode] [--iters N] [--max-cases N]
        [--cache PATH] [--verify-dispatch]
"""
from __future__ import annotations

import dataclasses
import inspect
import itertools
import json
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

#: env var overriding the default on-disk cache location
CACHE_ENV = "REPRO_TUNING_CACHE"

#: the default cache: a file of the package, so a checkout carries its
#: winners and nothing outside it steers the kernels
DEFAULT_CACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tuning.json")

#: swept values per tunable knob, per kernel. Candidate order is
#: deterministic (itertools.product over this table), default first.
TUNABLES: Dict[str, Dict[str, Tuple[int, ...]]] = {
    "flash_attention": {"block_q": (64, 128, 256),
                        "block_k": (64, 128, 256)},
    "lora_matmul": {"block_m": (64, 128, 256, 512, 1024),
                    "block_n": (128, 256, 512, 896),
                    "block_k": (128, 256, 512, 896)},
    "flash_decode": {"block_k": (64, 128, 256, 512)},
    "moe_expert_ffn": {"block_c": (64, 128, 256),
                       "block_f": (128, 256, 512)},
}

#: the kernels' built-in defaults (must mirror the wrapper signatures
#: in ``repro.kernels.ops``; pinned by tests/test_autotune.py). ``None``
#: is "derived from the call's shape" (``lora_matmul``'s blocks).
DEFAULTS: Dict[str, Dict[str, Optional[int]]] = {
    "flash_attention": {"block_q": 128, "block_k": 128},
    "lora_matmul": {"block_m": None, "block_n": None, "block_k": None},
    "flash_decode": {"block_k": 128},
    "moe_expert_ffn": {"block_c": 128, "block_f": 256},
}


def default_cache_path() -> str:
    return os.environ.get(CACHE_ENV) or DEFAULT_CACHE


def shape_key(args: Sequence) -> str:
    """Canonical key for one call's positional operands — shapes and
    dtypes only, so it works identically on concrete arrays, tracers
    and ``ShapeDtypeStruct``s (dispatch looks entries up at trace
    time)."""
    parts = []
    for a in args:
        shape = getattr(a, "shape", None)
        if shape is None:
            continue
        dt = getattr(a, "dtype", None)
        parts.append("x".join(str(d) for d in shape) + ":" + str(dt))
    return "|".join(parts)


def layout_signature(name: str) -> str:
    """The staleness key for kernel ``name``'s cache bucket: the
    declared ``*_layout()`` adapter's python signature. A renamed or
    added knob changes it and invalidates every cached entry."""
    from repro.kernels import dispatch

    fn = dispatch.kernel_layouts().get(name)
    return str(inspect.signature(fn)) if fn is not None else ""


# ---------------------------------------------------------------------------
# tuning cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TuningCache:
    """Platform-keyed winner store::

        {platform: {kernel: {"layout_sig": str,
                             "entries": {shape_key: {"config": {...},
                                                     "us": float,
                                                     "default_us": float}}}}}
    """

    path: str
    data: Dict = dataclasses.field(default_factory=dict)

    @classmethod
    def load(cls, path: Optional[str] = None) -> "TuningCache":
        path = path or default_cache_path()
        data: Dict = {}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    data = json.load(f)
            except (json.JSONDecodeError, OSError):
                data = {}        # a corrupt cache is a miss, never a crash
        return cls(path=path, data=data)

    def save(self) -> str:
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        with open(self.path, "w") as f:
            json.dump(self.data, f, indent=1, sort_keys=True)
            f.write("\n")
        return self.path

    def lookup(self, platform: str, kernel: str, key: str,
               layout_sig: str) -> Optional[Dict[str, int]]:
        """The tuned config for one (platform, kernel, shape) — or None
        on a miss / a stale ``layout_sig`` (the dispatch fallback: the
        kernel's built-in default blocks)."""
        bucket = self.data.get(platform, {}).get(kernel)
        if not bucket or bucket.get("layout_sig") != layout_sig:
            return None
        entry = bucket.get("entries", {}).get(key)
        return dict(entry["config"]) if entry else None

    def store(self, platform: str, kernel: str, layout_sig: str,
              key: str, config: Dict[str, int], us: float,
              default_us: float) -> None:
        bucket = self.data.setdefault(platform, {}).setdefault(
            kernel, {"layout_sig": layout_sig, "entries": {}})
        if bucket.get("layout_sig") != layout_sig:
            # the kernel's knobs changed shape: every old entry is
            # unusable, drop the bucket wholesale
            bucket["layout_sig"] = layout_sig
            bucket["entries"] = {}
        bucket["entries"][key] = {"config": dict(config),
                                  "us": float(us),
                                  "default_us": float(default_us)}


# ---------------------------------------------------------------------------
# candidate enumeration (layout-mediated, lint-filtered)
# ---------------------------------------------------------------------------


def candidate_configs(name: str, layout_fn: Callable, args: Sequence,
                      static: Dict) -> List[Dict[str, int]]:
    """Deterministic candidate list for one (kernel, shape): default
    config first, then the TUNABLES product — each materialized through
    the declared layout adapter, deduped on the derived ``BlockLayout``
    (``tile_block_cap`` collapses oversize blocks) and dropped if the
    L003 lint rejects it."""
    from repro.analysis.lowered.layout_lint import lint_layout

    defaults = DEFAULTS[name]
    knobs = TUNABLES[name]
    combos = [dict(defaults)]
    for values in itertools.product(*knobs.values()):
        combos.append({**defaults, **dict(zip(knobs, values))})
    seen = set()
    out: List[Dict[str, int]] = []
    for cfg in combos:
        try:
            layout = layout_fn(*args, **{**static, **cfg})
        except Exception:
            continue
        if lint_layout(layout):
            continue
        fingerprint = repr(layout)
        if fingerprint in seen:
            continue
        seen.add(fingerprint)
        out.append(cfg)
    return out


# ---------------------------------------------------------------------------
# measurement + selection
# ---------------------------------------------------------------------------


def measure_us(fn: Callable, args: Sequence, kwargs: Dict, *,
               iters: int, warmup: int = 1) -> float:
    """Wall time per call in microseconds, compile/warm-up excluded."""
    import jax

    out = None
    for _ in range(max(1, warmup)):
        out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


@dataclasses.dataclass
class TuneResult:
    kernel: str
    tag: str
    key: str
    config: Dict[str, int]
    us: float
    default_us: float
    n_candidates: int

    @property
    def is_default(self) -> bool:
        return self.config == DEFAULTS[self.kernel]


def tune_case(name: str, tag: str, args: Sequence, static: Dict,
              operands: Dict, *, iters: int = 10,
              measure: Callable = measure_us) -> Optional[TuneResult]:
    """Sweep one (kernel, shape): time every lint-valid candidate and
    return the winner. Selection is deterministic under a fixed
    ``measure`` injection: candidates are enumerated in a fixed order
    with the default first, and only a STRICT improvement displaces the
    incumbent — so the result is never slower than the default blocks,
    and ties resolve to the default."""
    import jax

    from repro.kernels import dispatch

    layout_fn = dispatch.kernel_layouts().get(name)
    if layout_fn is None or name not in TUNABLES:
        return None
    impl = dispatch.get_kernel(name, "pallas", tuned=False)
    interp = dispatch.interpret_default()
    candidates = candidate_configs(name, layout_fn, args, static)
    if not candidates:
        return None
    best_cfg: Optional[Dict[str, int]] = None
    best_us = default_us = 0.0
    for cfg in candidates:
        fn = jax.jit(lambda *a, _c=cfg, **kw: impl(
            *a, **static, **_c, interpret=interp, **kw))
        us = measure(fn, args, operands, iters=iters)
        if best_cfg is None:
            best_cfg, best_us, default_us = cfg, us, us
        elif us < best_us:
            best_cfg, best_us = cfg, us
    return TuneResult(kernel=name, tag=tag, key=shape_key(args),
                      config=best_cfg, us=best_us, default_us=default_us,
                      n_candidates=len(candidates))


# ---------------------------------------------------------------------------
# shape-family driver (the CLI path)
# ---------------------------------------------------------------------------


def _materialize(avals: Dict) -> Dict:
    """Concrete operands for a contract shape case: keyed normal noise
    for floats; int operands (``kv_valid_len``) fill with a ragged
    ramp capped to the cache capacity, so masking work is exercised."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    out: Dict = {}
    for i, (name, sds) in enumerate(avals.items()):
        if jnp.issubdtype(sds.dtype, jnp.integer):
            n = sds.shape[0] if sds.shape else 1
            ramp = 1 + jnp.arange(n, dtype=sds.dtype) * 7 % 64
            out[name] = ramp.reshape(sds.shape)
        else:
            out[name] = jax.random.normal(jax.random.fold_in(key, i),
                                          sds.shape, sds.dtype)
    return out


def autotune(kernels: Optional[Sequence[str]] = None, *,
             cache: Optional[TuningCache] = None, iters: int = 10,
             max_cases: Optional[int] = None,
             measure: Callable = measure_us) -> List[TuneResult]:
    """Sweep every tunable kernel over its contract shape family
    (``repro.analysis.contracts.shapes`` — the same shapes the bench
    and the C001/L003 layers iterate) and record winners in ``cache``."""
    import jax

    from repro.analysis.contracts import shapes
    from repro.kernels import dispatch

    platform = jax.default_backend()
    results: List[TuneResult] = []
    contracts = dispatch.kernel_contracts()
    names = list(kernels) if kernels else sorted(TUNABLES)
    for name in names:
        if name not in TUNABLES or name not in contracts:
            continue
        if "pallas" not in dispatch.available_kernels().get(name, []):
            continue
        sig = layout_signature(name)
        cases = list(shapes.kernel_cases(contracts[name].family))
        if max_cases is not None:
            cases = cases[:max_cases]
        for tag, arg_avals, kwargs in cases:
            static = {k: v for k, v in kwargs.items()
                      if not isinstance(v, jax.ShapeDtypeStruct)}
            op_avals = {k: v for k, v in kwargs.items()
                        if isinstance(v, jax.ShapeDtypeStruct)}
            args = list(_materialize(arg_avals).values())
            operands = _materialize(op_avals)
            res = tune_case(name, tag, args, static, operands,
                            iters=iters, measure=measure)
            if res is None:
                continue
            results.append(res)
            if cache is not None:
                cache.store(platform, name, sig, res.key, res.config,
                            res.us, res.default_us)
    return results


def _verify_dispatch(cache: TuningCache) -> int:
    """Prove the dispatch layer consumes this cache: for every stored
    entry, the tuned-config lookup that ``get_kernel``'s wrapper
    performs must return exactly the stored config. Returns the number
    of verified entries (raises on any mismatch)."""
    import jax

    from repro.kernels import dispatch

    dispatch.set_tuning_cache(cache)
    try:
        platform = jax.default_backend()
        n = 0
        for kernel, bucket in cache.data.get(platform, {}).items():
            for key, entry in bucket.get("entries", {}).items():
                got = dispatch.tuned_config(kernel, key=key)
                if got != entry["config"]:
                    raise AssertionError(
                        f"dispatch lookup for {kernel}[{key}] returned "
                        f"{got!r}, cache holds {entry['config']!r}")
                n += 1
        return n
    finally:
        dispatch.set_tuning_cache(None)


def main(argv=None) -> int:
    import argparse

    from repro.launch.env import setup_environment

    setup_environment()
    ap = argparse.ArgumentParser(
        description="sweep Pallas kernel block sizes; persist winners "
                    "to the platform-keyed tuning cache")
    ap.add_argument("--kernels", default=None,
                    help="comma-separated subset (default: all tunable)")
    ap.add_argument("--iters", type=int, default=10,
                    help="timed iterations per candidate (a warm-up "
                         "call is always excluded)")
    ap.add_argument("--max-cases", type=int, default=None,
                    help="limit shape cases per kernel (CI smoke)")
    ap.add_argument("--cache", default=None,
                    help=f"cache path (default ${CACHE_ENV} or "
                         f"src/repro/kernels/tuning.json)")
    ap.add_argument("--verify-dispatch", action="store_true",
                    help="after the sweep, assert dispatch resolves "
                         "every stored entry to its tuned config")
    args = ap.parse_args(argv)

    import jax

    cache = TuningCache.load(args.cache)
    names = args.kernels.split(",") if args.kernels else None
    results = autotune(names, cache=cache, iters=args.iters,
                       max_cases=args.max_cases)
    path = cache.save()
    interp = " (interpret mode — timings are NOT kernel performance)" \
        if jax.default_backend() != "tpu" else ""
    print(f"platform={jax.default_backend()}{interp}")
    print("kernel,shape,default_us,best_us,config,gain")
    for r in results:
        gain = "default" if r.is_default \
            else f"{r.default_us / r.us:.2f}x"
        cfg = ";".join(f"{k}={v}" for k, v in sorted(r.config.items()))
        print(f"{r.kernel},{r.tag},{r.default_us:.1f},{r.us:.1f},"
              f"{cfg},{gain}")
    print(f"# wrote {path} ({len(results)} entries)")
    if args.verify_dispatch:
        n = _verify_dispatch(cache)
        print(f"# dispatch consume check: {n} entries verified")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
