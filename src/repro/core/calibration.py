"""Calibration: measure REAL model serving to parameterize the simulator.

The paper measures MXNet forward passes inside Lambda; we measure the same
models' JAX forward passes on this host — and, since PR 7, the modern
serving stack too: ``repro.serving.engine.InferenceEngine`` and
``repro.serving.continuous.ContinuousServer`` are driven over tiny-scaled
registry configs (``repro.configs.registry``) to record per-model phase
costs and batch-efficiency curves.  Results feed ``repro.core.function``
handlers so scenario verdicts are per-model, not one-size.

Cache schema (v2) — versioned and host-fingerprinted::

    {"schema_version": 2,
     "host": {"node": ..., "machine": ..., "system": ..., "python": ...,
              "jax": ..., "backend": ...},
     "models": {
       "<cnn>": {"kind": "cnn",
                 "warm_exec_s":  steady-state jit'd prediction seconds,
                 "first_call_s": compile+first-call seconds},
       "<llm>": {"kind": "llm",
                 "warm_exec_s": steady generate (prefill+decode) seconds,
                 "init_s":      param init/load wall seconds,
                 "compile_s":   jit compile wall ("modern cold LOAD"),
                 "package_mb":  parameter bytes / 1e6,
                 "tokens_per_s": steady decode throughput,
                 "batch_curve": [[batch, rel_per_request_cost], ...]
                                measured from ContinuousServer}}}

``load_cache`` REFUSES a cache whose schema version or host fingerprint
does not match (returns None → callers re-measure); it never silently
mixes hosts.  The cache lives at ``artifacts/calibration.json`` (anchored
to the repo root, overridable via ``REPRO_CALIBRATION`` — read at call
time by ``default_cal_path()``; the old ``CAL_PATH`` module constant is
deprecated precisely because it snapshotted that env var at import).

CLI::

    python -m repro.core.calibration --models deepseek-7b resnet18 [--force]
"""
from __future__ import annotations

import argparse
import json
import os
import platform as _platform
import time
import warnings

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core.function import Handler, batch_rel_cost, normalize_batch_curve
from repro.models import cnn
from repro.models.common import ModelConfig, param_bytes

SCHEMA_VERSION = 2

# Calibration cache location.  Anchored to the repo root (NOT the process
# cwd — a cwd-relative path silently re-measured whenever a benchmark ran
# from another directory, producing host-dependent "deterministic" runs).
# Override with the REPRO_CALIBRATION env var (read at call time, so tests
# and deploy scripts can point at a pre-measured file).
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def default_cal_path() -> str:
    return os.environ.get("REPRO_CALIBRATION") or \
        os.path.join(_REPO_ROOT, "artifacts", "calibration.json")


def __getattr__(name):
    # CAL_PATH used to be a module-load snapshot of default_cal_path(),
    # which silently ignored REPRO_CALIBRATION set after import.  Keep the
    # attribute working (computed at access time now) but steer callers to
    # the function.
    if name == "CAL_PATH":
        warnings.warn(
            "repro.core.calibration.CAL_PATH is deprecated: it was a "
            "module-load snapshot that ignored REPRO_CALIBRATION set after "
            "import; call default_cal_path() instead",
            DeprecationWarning, stacklevel=2)
        return default_cal_path()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# paper §3 ground truth per model: (package MB, peak memory MB, 2017-era
# full-CPU prediction seconds used if no local calibration is available)
PAPER_MODELS = {
    "squeezenet": {"package_mb": 5.0, "peak_mb": 85.0, "fallback_s": 0.22},
    "resnet18": {"package_mb": 45.0, "peak_mb": 229.0, "fallback_s": 0.35},
    "resnext50": {"package_mb": 98.0, "peak_mb": 429.0, "fallback_s": 0.80},
}

# jax + XLA runtime import at one full CPU — the modern BOOTSTRAP analogue
# of the paper's 1.2 s MXNet import.
MODERN_BOOTSTRAP_CPU_S = 1.0

# Modern registry models the suite can deploy without a local measurement
# pass: ``fallback`` entries were measured once on the reference dev host
# (smoke-scaled configs, CPU) and rounded — they keep fallback-calibration
# runs (CI, tests, the deterministic suite verdicts) host-independent,
# exactly like PAPER_MODELS' ``fallback_s``.  ``peak_mb`` is the declared
# working set for deploy-time OOM validation.  Numbers are from the fused
# decode path (scan generate / fused ContinuousServer steps — the engines
# these stand in for); ``warm_exec_s`` halved and ``tokens_per_s`` roughly
# doubled vs the per-token-loop era they replaced.
MODERN_MODELS = {
    "deepseek-7b": {
        "peak_mb": 512.0,
        "fallback": {"kind": "llm", "warm_exec_s": 0.0045, "init_s": 1.83,
                     "compile_s": 0.92, "package_mb": 1.84,
                     "tokens_per_s": 2039.0,
                     "batch_curve": [[1, 1.0], [2, 0.45], [4, 0.22]]},
    },
    "qwen2.5-32b": {
        "peak_mb": 512.0,
        "fallback": {"kind": "llm", "warm_exec_s": 0.0048, "init_s": 2.05,
                     "compile_s": 0.85, "package_mb": 1.71,
                     "tokens_per_s": 1595.0,
                     "batch_curve": [[1, 1.0], [2, 0.36], [4, 0.21]]},
    },
    "qwen3-moe-235b-a22b": {
        "peak_mb": 768.0,
        "fallback": {"kind": "llm", "warm_exec_s": 0.0037, "init_s": 1.0,
                     "compile_s": 1.41, "package_mb": 1.71,
                     "tokens_per_s": 2599.0,
                     "batch_curve": [[1, 1.0], [2, 0.50], [4, 0.24]]},
    },
    "rwkv6-1.6b": {   # non-transformer: no ContinuousServer batch curve
        "peak_mb": 384.0,
        "fallback": {"kind": "llm", "warm_exec_s": 0.006, "init_s": 1.32,
                     "compile_s": 1.39, "package_mb": 2.31,
                     "tokens_per_s": 1355.0, "batch_curve": []},
    },
    # the sharded_110b scenario's model: too big for one sandbox at real
    # scale, so the distributed-inference path fans it out (smoke-scaled
    # measurements like the rest; peak_mb is the FULL single-sandbox
    # working set the ShardPlan's memory fractions divide)
    "qwen1.5-110b": {
        "peak_mb": 768.0,
        "fallback": {"kind": "llm", "warm_exec_s": 0.0080, "init_s": 2.48,
                     "compile_s": 1.18, "package_mb": 3.46,
                     "tokens_per_s": 1180.0,
                     "batch_curve": [[1, 1.0], [2, 0.52], [4, 0.27]]},
    },
}

# re-exported for the property tests / external callers
batch_efficiency = batch_rel_cost


# ------------------------------------------------------------- cache schema
def host_fingerprint() -> dict:
    """Identity of the measuring host.  A cache written under a different
    fingerprint is refused (re-measured), never silently mixed in."""
    return {"node": _platform.node(),
            "machine": _platform.machine(),
            "system": _platform.system(),
            "python": _platform.python_version(),
            "jax": jax.__version__,
            "backend": jax.default_backend()}


def new_cache() -> dict:
    return {"schema_version": SCHEMA_VERSION, "host": host_fingerprint(),
            "models": {}}


def load_cache(path: str | None = None, *, strict: bool = True):
    """Load a calibration cache, or None when it must be re-measured.

    Returns None — never raises — for a missing/corrupt file, a schema
    version other than ``SCHEMA_VERSION`` (v1 caches had neither version
    nor fingerprint), or (under ``strict``, the default) a host
    fingerprint that does not match this host."""
    path = path or default_cal_path()
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            raw = json.load(f)
    except (ValueError, OSError):
        return None
    if not isinstance(raw, dict) or \
            raw.get("schema_version") != SCHEMA_VERSION or \
            not isinstance(raw.get("models"), dict):
        return None
    if strict and raw.get("host") != host_fingerprint():
        return None
    return raw


def save_cache(cache: dict, path: str | None = None) -> str:
    path = path or default_cal_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(cache, f, indent=1, sort_keys=True)
    return path


# -------------------------------------------------------------- measurement
def _measure_cnn(variant: str, image_size: int = 224,
                 repeats: int = 5) -> dict:
    cfg = ModelConfig(name=variant, family="cnn", cnn_variant=variant,
                      image_size=image_size, param_dtype="float32",
                      compute_dtype="float32")
    params = cnn.init_params(jax.random.PRNGKey(0), cfg)
    img = jnp.zeros((1, image_size, image_size, 3), jnp.float32)
    fwd = jax.jit(lambda p, x: cnn.forward(p, x, cfg))
    t0 = time.perf_counter()
    fwd(params, img).block_until_ready()
    first = time.perf_counter() - t0
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fwd(params, img).block_until_ready()
        times.append(time.perf_counter() - t0)
    times.sort()
    return {"kind": "cnn", "warm_exec_s": times[len(times) // 2],
            "first_call_s": first}


def _measure_batch_curve(cfg: ModelConfig, *, batches=(1, 2, 4),
                         prompt: int = 8, steps: int = 6,
                         seed: int = 0) -> list:
    """Per-request fused-decode cost vs batch size, from the real
    ``ContinuousServer``: pin exactly ``b`` active slots, take one untimed
    step (fused-decode compile for that slot count), then time ``steps``
    fused steps.  Points are normalized (rel cost at batch 1 = 1.0) and
    clamped monotone by ``normalize_batch_curve``."""
    from repro.serving.continuous import ContinuousServer, Request
    points = []
    for b in batches:
        srv = ContinuousServer(cfg, slots=int(b),
                               max_seq=prompt + steps + 4, seed=seed)
        for i in range(int(b)):
            srv.submit(Request(rid=i, prompt=[1 + i] * prompt,
                               n_new=steps + 3))
        srv.prefill_pending()
        assert srv.n_active() == int(b)
        srv.step()                              # untimed: compile
        t0 = time.perf_counter()
        for _ in range(steps):
            srv.step()
        wall = (time.perf_counter() - t0) / steps
        points.append((int(b), wall / b))       # per-request share
    return [[b, r] for b, r in normalize_batch_curve(points)]


def _measure_llm(cfg: ModelConfig, *, prompt: int = 16, n_new: int = 8,
                 repeats: int = 3, seed: int = 0) -> dict:
    from repro.serving.engine import InferenceEngine
    t0 = time.perf_counter()
    eng = InferenceEngine(cfg, seed=seed, max_cache=prompt + n_new + 8)
    init_s = time.perf_counter() - t0
    compile_s = eng.warmup(1, prompt)
    toks = jnp.zeros((1, prompt), jnp.int32)
    walls, tps = [], 0.0
    for _ in range(repeats):
        res = eng.generate(toks, n_new)
        walls.append(res.prefill_s + res.decode_s)
        tps = res.tokens_per_s
    walls.sort()
    curve = []
    if cfg.family in ("dense", "moe", "vlm"):
        curve = _measure_batch_curve(cfg, seed=seed)
    return {"kind": "llm",
            "warm_exec_s": walls[len(walls) // 2],
            "init_s": init_s,
            "compile_s": compile_s,
            "package_mb": param_bytes(eng.params) / 1e6,
            "tokens_per_s": tps,
            "batch_curve": curve}


def measure_model(name: str, **measure_kw) -> dict:
    """Measure one model on this host: a paper CNN by name, or any
    ``repro.configs.registry`` arch id (measured at its tiny ``smoke``
    config — the full configs do not fit a CPU dev host)."""
    if name in PAPER_MODELS:
        return _measure_cnn(name, **measure_kw)
    from repro.configs import registry
    try:
        spec = registry.get(name)
    except KeyError:
        raise KeyError(
            f"unknown model {name!r}; paper CNNs: {sorted(PAPER_MODELS)}, "
            f"registry archs: {sorted(registry.ALL)}") from None
    return _measure_llm(spec.smoke, **measure_kw)


# ---------------------------------------------------------------- calibrate
def calibrate(path: str | None = None, force: bool = False, *,
              models=None, strict: bool = True) -> dict:
    """Load-or-measure the calibration cache; returns the full v2 cache.

    A cache that fails ``load_cache``'s version/fingerprint checks is
    re-measured from scratch (the refusal semantics: stale or foreign
    numbers are never mixed with this host's).  ``models`` selects what
    must be present (default: the three paper CNNs); anything already
    measured is kept, anything missing is measured and the file updated."""
    path = path or default_cal_path()
    cache = None if force else load_cache(path, strict=strict)
    fresh = cache is None
    if fresh:
        cache = new_cache()
    wanted = list(models) if models is not None else list(PAPER_MODELS)
    missing = [m for m in wanted if m not in cache["models"]]
    for m in missing:
        cache["models"][m] = measure_model(m)
    if fresh or missing:
        save_cache(cache, path)
    return cache


def ensure_measured(cache, name: str, path: str | None = None) -> dict:
    """Return a cache that contains ``name``, measuring (and persisting)
    it if absent.  ``cache=None`` loads-or-creates first."""
    if cache is None:
        cache = load_cache(path) or new_cache()
    if name not in cache["models"]:
        cache["models"][name] = measure_model(name)
        save_cache(cache, path)
    return cache


# ----------------------------------------------------------------- handlers
def _entries(calibrated) -> dict:
    """Model entries from a v2 cache, a bare entries dict, or a legacy v1
    flat ``{model: {base_cpu_seconds, ...}}`` dict."""
    if calibrated is None:
        return {}
    return calibrated.get("models", calibrated)


def paper_handler(variant: str, *, calibrated: dict | None = None,
                  use_fallback: bool = False) -> Handler:
    info = PAPER_MODELS[variant]
    base = info["fallback_s"]
    if not use_fallback:
        entry = _entries(calibrated).get(variant) or {}
        base = entry.get("warm_exec_s",          # v2
                         entry.get("base_cpu_seconds", base))  # legacy v1
    return Handler(
        name=variant,
        base_cpu_seconds=float(base),
        bootstrap_cpu_seconds=1.2,          # MXNet import + runtime init
        package_mb=info["package_mb"],
        peak_memory_mb=info["peak_mb"],
    )


def modern_handler(name: str, *, calibrated: dict | None = None,
                   use_fallback: bool = False) -> Handler:
    """A Handler for a modern registry model, built from measured (or
    pinned-fallback) engine numbers: warm exec = steady generate, LOAD
    gains the measured param-init + jit-compile as CPU-bound work, and the
    ``ContinuousServer`` batch-efficiency curve rides along for the
    cluster's batching path."""
    info = MODERN_MODELS.get(name)
    entry = None if use_fallback else _entries(calibrated).get(name)
    if entry is None:
        if info is None:
            raise KeyError(
                f"no fallback calibration for {name!r} (pinned: "
                f"{sorted(MODERN_MODELS)}); measure it first via "
                f"calibrate(models=[{name!r}])")
        entry = info["fallback"]
    peak = info["peak_mb"] if info else max(
        128.0, 2.0 * float(entry["package_mb"]) + 64.0)
    curve = tuple((int(b), float(r))
                  for b, r in entry.get("batch_curve") or ())
    return Handler(
        name=name,
        base_cpu_seconds=float(entry["warm_exec_s"]),
        bootstrap_cpu_seconds=MODERN_BOOTSTRAP_CPU_S,
        package_mb=float(entry["package_mb"]),
        peak_memory_mb=float(peak),
        load_cpu_seconds=float(entry["init_s"]) + float(entry["compile_s"]),
        batch_curve=curve,
    )


# ---------------------------------------------------------------------- CLI
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Measure models on this host and update the "
                    "calibration cache (schema v2, host-fingerprinted).")
    ap.add_argument("--models", nargs="+", default=None, metavar="NAME",
                    help="paper CNNs and/or registry arch ids (default: "
                         "the three paper CNNs)")
    ap.add_argument("--path", default=None,
                    help="cache file (default: default_cal_path())")
    ap.add_argument("--force", action="store_true",
                    help="discard any existing cache and re-measure")
    args = ap.parse_args(argv)
    enable_compile_cache()
    cache = calibrate(args.path, args.force, models=args.models)
    print(f"calibration cache: {args.path or default_cal_path()}")
    print(f"host: {cache['host']}")
    for name in sorted(cache["models"]):
        e = cache["models"][name]
        extra = ""
        if e.get("kind") == "llm":
            extra = (f"  init={e['init_s']:.3f}s compile={e['compile_s']:.3f}s"
                     f"  curve={e.get('batch_curve')}")
        print(f"  {name:24s} warm={e['warm_exec_s']:.4f}s{extra}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
