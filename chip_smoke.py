"""Chip smoke: serve qwen2-1.5b at its published widths on one TPU.

    python chip_smoke.py

One process, start to end. It names the device and fails unless it is a
TPU; resolves the chip's hardware descriptor from its ``device_kind``;
compiles the qwen2-1.5b bf16 serving plan cells with the analytic sweep;
serves a few random-weight requests through the normal launcher
(``repro.launch.serve.main``) on the paged + packed + bucketed path; checks
that every kernel site that has a Pallas kernel and a resolved tile ran the
kernel; and compares one prompt's logits between the Pallas lowering and
the plain one (see LOGITS_RTOL). Any failed phase exits nonzero. The last line of stdout is
one JSON object naming the device, printed only when every phase passed.

The plan artifact goes to ``chiprun_out/chip_smoke/plans.json``; the
compile cache to ``$JAX_COMPILATION_CACHE_DIR`` or ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen2-1.5b"
DTYPE = "bfloat16"
BUCKETS = (128, 512)
MAX_LEN = 1024
SLOTS = 4
OUT_DIR = ROOT / "chiprun_out" / "chip_smoke"

# (kernel, phase) sites of the served path. Sites with a Pallas kernel must
# run it wherever a tile resolved; packed and paged-chunk prefill have no
# kernel yet and always lower to their reference.
KERNEL_SITES = (("flash_decode", "decode"), ("matmul", "decode"),
                ("matmul", "prefill"))
REFERENCE_SITES = (("chunked_prefill", "prefill"),
                   ("packed_prefill", "prefill"))

# Pallas vs plain lowering: max |logit difference| over max |plain logit|
# after a bucket-length prefill and after one decode step. The gate runs on
# a depth-cut copy of the model, CHECK_LAYERS layers at the published widths
# with the same plan tiles: every layer of the full model is the same
# scanned body, the same kernels at the same tiles, so depth adds nothing
# this comparison could catch, while with random weights it amplifies bf16
# rounding differences chaotically (on a TPU v5e: 0.5-0.9% at two layers,
# 6-8% at 28). The two lowerings compute in bf16 with float32 accumulation
# and differ only in summation order and in where intermediates round to
# bf16 (2^-9 relative each). 3% is about three times the two-layer
# distance and still fails a wrong mask, block index or dropped block,
# which move the logits by their own order (another prompt's logits differ
# by more than 100%). The full-depth distance prints for scale.
CHECK_LAYERS = 2
LOGITS_RTOL = 0.03


class CompileClock:
    """Sums the backend compile seconds JAX reports, and cache hits."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def compile_plan_artifact(hw, out_dir: Path, *, full: bool = True,
                          buckets=BUCKETS, max_len: int = MAX_LEN,
                          slots: int = SLOTS, dtype: str = DTYPE) -> Path:
    """Compile the serving plan cells of ``ARCH`` for ``hw`` with the
    analytic sweep and write the artifact. Every cell must compile."""
    from repro import kernels
    from repro.core.plans import compile_plan
    from repro.launch.compile_plans import serve_bucket_cells

    kernels.register_all()
    cells = serve_bucket_cells([ARCH], buckets, slots, max_len,
                               smoke=not full)
    plan = compile_plan([(k, p, dtype, hw) for k, p in cells],
                        meta={"generated_by": "chip_smoke",
                              "archs": [ARCH], "dtypes": [dtype],
                              "serve_buckets": list(buckets),
                              "measure": "analytic"})
    if plan.meta["skipped_jobs"] or len(plan) != len(cells):
        raise RuntimeError(f"plan compiled {len(plan)} of {len(cells)} "
                           f"cells")
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / "plans.json"
    plan.save(str(path))
    return path


def site_table(events, metrics):
    """One row per kernel site: the lowerings its compiled programs used
    and the engine's tile_fallback count for the kernel."""
    by_kernel = metrics["plan"]["by_kernel"]
    rows = []
    for kernel, phase in KERNEL_SITES + REFERENCE_SITES:
        impls = sorted({e["impl"] for e in events
                        if e["kernel"] == kernel and e["phase"] == phase})
        tiles = sorted({str(e["tile"]) for e in events
                        if e["kernel"] == kernel and e["phase"] == phase})
        rows.append({"kernel": kernel, "phase": phase, "impl": impls,
                     "tiles": tiles,
                     "tile_fallback": by_kernel.get(kernel, {}).get(
                         "tile_fallback", 0)})
    return rows


def site_failures(rows):
    """The kernel sites that did not run their Pallas kernel on every
    program, or reported a tile fallback."""
    bad = []
    for row in rows:
        if (row["kernel"], row["phase"]) not in KERNEL_SITES:
            continue
        if row["impl"] != ["pallas"] or row["tile_fallback"]:
            bad.append(f"{row['kernel']}/{row['phase']}: ran "
                       f"{row['impl'] or 'nothing'}, tile_fallback "
                       f"{row['tile_fallback']}")
    return bad


def serve_phase(plans_path: Path, hw, *, full: bool = True,
                dtype: str = DTYPE, requests: int = 8, new_tokens: int = 32,
                prompt_len=(100, 500), buckets=BUCKETS,
                max_len: int = MAX_LEN, slots: int = SLOTS):
    """Serve ``requests`` random prompts through ``serve.main`` on the
    paged + packed + bucketed path, capturing every tile event. Returns
    ``(serve result, site rows, failures)``."""
    from repro.launch import serve
    from repro.models import attention

    argv = ["--arch", ARCH, "--dtype", dtype,
            "--requests", str(requests), "--new-tokens", str(new_tokens),
            "--prompt-len", f"{prompt_len[0]},{prompt_len[1]}",
            "--paged", "--pack-prefill", "--scheduler", "bucket",
            "--bucket-policy", ",".join(str(b) for b in buckets),
            "--max-len", str(max_len), "--slots", str(slots),
            "--tile-plans", str(plans_path), "--hardware", hw.name]
    if full:
        argv.append("--full")
    events = []
    with attention.capture_tile_events(events.append):
        result = serve.main(argv)
    failures = []
    done = result["requests"]
    if len(done) != requests or result["rejected"]:
        failures.append(f"{len(done)} of {requests} requests completed, "
                        f"{result['rejected']} rejected")
    short = [r.rid for r in done if len(r.out_tokens) != new_tokens]
    if short:
        failures.append(f"requests {short} did not produce {new_tokens} "
                        f"tokens")
    rows = site_table(events, result["metrics"])
    failures += site_failures(rows)
    return result, rows, failures


def pallas_vs_plain(cfg, params, plans, hw, *, dtype: str = DTYPE,
                    prompt_len: int = BUCKETS[-1], max_len: int = MAX_LEN,
                    slots: int = SLOTS, seed: int = 1):
    """Prefill one prompt and decode one token with the plan's tiles (the
    Pallas kernels) and with ``tiles=None`` (the plain lowering). Returns
    the relative logit differences and the tiled run's kernel lowerings."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.specs import resolve_model_tiles
    from repro.models import api, attention

    prefill_tiles, _ = resolve_model_tiles(plans, cfg, 1, prompt_len,
                                           "prefill", dtype, hw)
    decode_tiles, _ = resolve_model_tiles(plans, cfg, slots, max_len,
                                          "decode", dtype, hw)
    rng = np.random.default_rng(seed)
    prompts = rng.integers(2, cfg.vocab_size, (2, 1, prompt_len))

    def run(tokens, ptiles, dtiles):
        pre = jax.jit(lambda p, t: api.prefill(
            p, cfg, {"tokens": t}, max_len=max_len, dtype=dtype,
            tiles=ptiles))
        dec = jax.jit(lambda p, t, st: api.decode_step(p, cfg, t, st,
                                                       tiles=dtiles))
        last, state = pre(params, jnp.asarray(tokens, jnp.int32))
        nxt = jnp.asarray(tokens[:, :1], jnp.int32)
        logits, _ = dec(params, nxt, state)
        v = cfg.vocab_size
        return (np.asarray(last[0, :v], np.float32),
                np.asarray(logits[0, :v], np.float32))

    events = []
    with attention.capture_tile_events(events.append):
        tiled = run(prompts[0], prefill_tiles, decode_tiles)
    plain = run(prompts[0], None, None)
    other = run(prompts[1], None, None)

    def rel(a, b):
        return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))

    lowerings = sorted({(e["kernel"], e["phase"], e["impl"])
                        for e in events})
    return {"prefill_rel": rel(tiled[0], plain[0]),
            "decode_rel": rel(tiled[1], plain[1]),
            "other_prompt_rel": rel(other[1], plain[1]),
            "lowerings": lowerings}


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}")
    print(f"devices: {devices}")
    print(f"platform: {dev.platform}  device_kind: {dev.device_kind}  "
          f"count: {len(devices)}")
    if dev.platform != "tpu":
        print(f"FAIL: no TPU (platform {dev.platform!r})", file=sys.stderr)
        return 2

    from repro import configs
    from repro.core.hardware import for_device_kind
    from repro.core.plans import TilePlan
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import init_params

    cache_dir = enable_compile_cache()
    print(f"compile cache: {cache_dir}")
    clock = CompileClock()
    hw = for_device_kind(dev.device_kind)
    cfg = configs.get_arch(ARCH)
    print(f"config: {ARCH} at published widths: {cfg.n_layers} layers, "
          f"d_model {cfg.d_model}, {cfg.n_heads} query / {cfg.n_kv_heads} "
          f"KV heads of {cfg.head_dim_}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}; dtype {DTYPE}; hardware descriptor {hw.name}")

    failures = []
    t0 = time.perf_counter()
    plans_path = compile_plan_artifact(hw, OUT_DIR)
    plans = TilePlan.load(str(plans_path))
    print(f"plan: {len(plans)} cells -> {plans_path.relative_to(ROOT)} "
          f"({time.perf_counter() - t0:.1f}s)")

    t0, c0 = time.perf_counter(), clock.seconds
    result, rows, serve_failures = serve_phase(plans_path, hw)
    failures += serve_failures
    done = result["requests"]
    print(f"serve: {len(done)} requests completed, {result['rejected']} "
          f"rejected, {sum(len(r.out_tokens) for r in done)} tokens "
          f"({[len(r.prompt) for r in done]} prompt tokens)")
    print(f"serve seconds (informational): wall "
          f"{time.perf_counter() - t0:.1f}, compile "
          f"{clock.seconds - c0:.1f}")
    print("kernel sites:")
    for row in rows:
        print(f"  {row['kernel']:16s} {row['phase']:8s} "
              f"{'/'.join(row['impl']) or 'not run':10s} "
              f"tile_fallback={row['tile_fallback']} "
              f"tiles={','.join(row['tiles'])}")

    want = {("flash_attention", "prefill", "pallas"),
            ("flash_decode", "decode", "pallas"),
            ("matmul", "prefill", "pallas"), ("matmul", "decode", "pallas")}
    for layers in (CHECK_LAYERS, cfg.n_layers):
        t0, c0 = time.perf_counter(), clock.seconds
        cut = dataclasses.replace(cfg, n_layers=layers).validate()
        check = pallas_vs_plain(cut, init_params(cut, DTYPE), plans, hw)
        gate = layers == CHECK_LAYERS
        print(f"pallas vs plain logits, {layers} layers "
              f"({'gate: tolerance ' + str(LOGITS_RTOL) if gate else 'for scale'}"
              f"): max|diff|/max|logit| decode {check['decode_rel']:.3e}, "
              f"prefill {check['prefill_rel']:.3e}; another prompt differs "
              f"by {check['other_prompt_rel']:.3e}")
        print(f"  tiled run lowerings: {check['lowerings']}")
        print(f"  seconds (informational): wall "
              f"{time.perf_counter() - t0:.1f}, compile "
              f"{clock.seconds - c0:.1f}")
        if set(check["lowerings"]) != want:
            failures.append(f"tiled check ran {check['lowerings']}")
        if gate and max(check["decode_rel"],
                        check["prefill_rel"]) > LOGITS_RTOL:
            failures.append("pallas and plain logits differ beyond "
                            "tolerance")
    print(f"compile (informational): {clock.compiles} programs, "
          f"{clock.seconds:.1f}s, {clock.cache_hits} persistent-cache hits")

    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
