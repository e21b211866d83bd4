"""Smoke run of the system's main path on one TPU v5e.

    python3 chip_smoke.py

Two phases run in order in this one process (a chip belongs to one
process at a time):

* ``campaign`` -- the DSE service, built as ``python -m repro.service``
  builds it (thread evaluation backend, fresh label store and synth cache
  in a temporary directory) and driven over HTTP through its ``Client``:
  a gaussian3x3 campaign at the ``CampaignSpec`` defaults, a hierarchical
  smoothed_dct job, then ``POST /serve`` requests to the serving tier.
  It fails unless the fused simulation ran on the device and matched the
  numpy engine byte for byte (no pins, no fallbacks), synthesis compiled
  on the TPU, both fronts are non-empty and every served QoR is finite.
* ``lm`` -- granite-8b at its published widths with its depth cut to fit
  one chip, served through ``repro.launch.serve.serve_batch`` with the
  exact policy and with an approximate policy of rank > 0.  The served
  programs' prefill and first decode-step logits are tied to the tokens
  ``generate`` emitted and checked against an independent float32
  forward on the host CPU.

Without a TPU, or outside a checkout of the repository, the script exits
non-zero before any phase.  The last line of standard output is the JSON
result, printed only when every phase passed.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# granite-8b keeps its published widths; only the depth is cut.  The
# projections, embedding and LM head are stored in bfloat16, ~0.44 GB per
# layer plus 0.8 GB of embedding and LM head.
GRANITE_LAYERS = 8
LM_BATCH, LM_PROMPT, LM_GEN = 8, 128, 16
# circuits with rank > 0 on the FFN projections (the rank-k MXU path)
APPROX = {"ffn_in": ("mul8s_drum4", 2), "ffn_out": ("mul8s_drum4", 2)}
# relative L2 error allowed between the served exact-policy logits, which
# the model computes in bfloat16, and the float32 host forward.  On the
# host CPU at granite's widths the bfloat16 error measured 1.8% at 2
# layers and 2.4% at 4, growing about as the square root of depth (~3.4%
# at 8).  The run also plants a fault -- the reference with its RoPE
# skipped -- and fails unless that lands outside the tolerance (on the
# CPU at d_model 1024 it lands 25-34% away; at d_model 256, where random
# attention is nearly uniform, only 5%, which is why the fault is checked
# at the width that is served)
LOGITS_RTOL = 6e-2
# the hierarchical job at the sizes of the service's verify recipe
HIER = dict(n_train=10, n_qor_samples=2, pop_size=8, n_parents=4,
            n_generations=2, k_per_stage=5, max_candidates=10)
CAMPAIGN_TIMEOUT_S = 900.0


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


# ---------------------------------------------------------------------------
# phase: campaign
# ---------------------------------------------------------------------------

def campaign_phase(workdir: str, *, gauss: dict, hier: dict) -> dict:
    from repro.accel import fused
    from repro.core.features import synth
    from repro.service.__main__ import build_manager, build_parser
    from repro.service.api import Client, make_server
    from repro.service.campaigns import make_accelerator

    args = build_parser().parse_args([
        "--port", "0", "--eval-backend", "thread",
        "--store", os.path.join(workdir, "labels.jsonl"),
        "--synth-cache", os.path.join(workdir, "synth.jsonl"),
        "--snapshots", os.path.join(workdir, "snapshots.jsonl"),
    ])
    manager = build_manager(args)
    server = make_server(manager, args.host, args.port)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        client = Client(f"http://{args.host}:{server.server_address[1]}")
        t0 = time.perf_counter()
        ids = {
            "gaussian3x3": client.submit(accel="gaussian3x3", **gauss),
            "smoothed_dct hierarchical": client.submit_hierarchical(
                accel="smoothed_dct", **hier),
        }
        fronts = {}
        for name, cid in ids.items():
            st = client.wait(cid, timeout=CAMPAIGN_TIMEOUT_S)
            _require(st["state"] == "done",
                     f"{name} campaign {cid} is {st['state']}: "
                     f"{st.get('error')}")
            fronts[name] = client.result(cid)["front"]
            _require(len(fronts[name]) > 0, f"{name} front is non-empty")
        wall = time.perf_counter() - t0
        say(f"campaign: both campaigns done in {wall:.3f} s; front sizes "
            + ", ".join(f"{k} {len(v)}" for k, v in fronts.items()))

        images = make_accelerator("gaussian3x3").sample_inputs(2, seed=7)
        t0 = time.perf_counter()
        for tier in ("exact", "balanced", "budget"):
            r = client.serve("gaussian3x3", images, tier=tier)
            _require(math.isfinite(r["qor"]),
                     f"serve tier={tier} measured a finite qor")
            say(f"campaign: serve gaussian3x3 tier={tier} genome="
                f"{r['genome']} qor={r['qor']}")
        serve_wall = time.perf_counter() - t0

        fs = fused.stats()
        ss = manager.synth_cache.stats()
        tag = synth.backend_tag()
        say(f"campaign: fused sim {json.dumps(fs, sort_keys=True)}")
        say(f"campaign: synthesis on {tag}: compiles {ss['compiles']}, "
            f"structures {ss['structures']}, hit rate {ss['hit_rate']}")
        _require(fs["fused_calls"] + fs["fused_qor_calls"] > 0,
                 "the fused simulation ran")
        _require(fs["verify_calls"] > 0,
                 "fused results were verified against the numpy engine")
        _require(fs["pins"] == 0, "no fused family diverged (pins == 0)")
        _require(fs["fallback_calls"] == 0,
                 "no fusible family ran the numpy engine")
        _require(tag.startswith("tpu:") and ss["compiles"] > 0,
                 "synthesis compiled on the TPU backend")
        return {"campaign_wall_s": wall, "serve_wall_s": serve_wall,
                "fused": fs, "synth_compiles": ss["compiles"]}
    finally:
        server.shutdown()
        server.server_close()
        manager.shutdown()


# ---------------------------------------------------------------------------
# phase: lm
# ---------------------------------------------------------------------------

def reference_logits(params, tokens, cfg, *, last: int, rope: bool = True):
    """Independent float32 forward of a dense llama-style decoder
    (RMSNorm with a (1 + scale) gain, interleaved-pair RoPE, grouped-query
    causal attention, SwiGLU MLP), run on the host CPU at HIGHEST matmul
    precision; returns the logits of the ``last`` final positions.
    ``rope=False`` skips the rotary embedding: a planted fault that shows
    how far a wrong program lands from the reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim

    def norm(x, scale):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + cfg.rms_eps) * (1.0 + scale)

    def forward(p, toks):
        b, s = toks.shape
        inv = 1.0 / cfg.rope_theta ** (
            jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
        cos, sin = jnp.cos(ang), jnp.sin(ang)

        def rot(t):
            if not rope:
                return t
            t1, t2 = t[..., 0::2], t[..., 1::2]
            return jnp.stack([t1 * cos - t2 * sin, t1 * sin + t2 * cos],
                             axis=-1).reshape(t.shape)

        def heads(t, n):
            return t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

        causal = jnp.tril(jnp.ones((s, s), bool))
        x = p["embed"][toks]
        blocks = p["blocks"]["layer0"]
        for i in range(cfg.n_layers):
            a = jax.tree.map(lambda t: t[i], blocks["attn"])
            m = jax.tree.map(lambda t: t[i], blocks["mlp"])
            h = norm(x, a["norm"])
            q = rot(heads(h @ a["wq"], H))
            k = jnp.repeat(rot(heads(h @ a["wk"], KV)), H // KV, axis=1)
            v = jnp.repeat(heads(h @ a["wv"], KV), H // KV, axis=1)
            sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) * hd ** -0.5
            sc = jnp.where(causal, sc, -jnp.inf)
            o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), v)
            x = x + o.transpose(0, 2, 1, 3).reshape(b, s, H * hd) @ a["wo"]
            h = norm(x, m["norm"])
            x = x + (jax.nn.silu(h @ m["wg"]) * (h @ m["wi"])) @ m["wo"]
        return norm(x[:, -last:], p["final_norm"]) @ p["lm_head"]

    cpu = jax.devices("cpu")[0]
    p = jax.tree.map(lambda t: t.astype(jnp.float32),
                     jax.device_put(params, cpu))
    toks = jax.device_put(tokens, cpu)
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(forward)(p, toks))


def _timed(fn, reps: int = 3) -> list:
    """Wall seconds of ``reps`` calls of an already compiled step, each
    synced to the device."""
    import jax

    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        out.append(time.perf_counter() - t0)
    return out


def lm_phase(cfg, *, batch: int, prompt_len: int, gen: int, approx: dict,
             rtol: float, hbm_bytes: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import serve_batch
    from repro.models import ApproxPolicy, cache_specs, param_specs
    from repro.models.common import init_tree
    from repro.train.serve import Generator

    policy = ApproxPolicy(approx)
    ranks = {cls: policy.spec(cls).rank for cls in approx}
    _require(all(r > 0 for r in ranks.values()),
             f"the approximate policy has rank > 0 ({ranks})")
    say(f"lm: {cfg.name} d_model {cfg.d_model}, heads {cfg.n_heads}/"
        f"{cfg.n_kv_heads}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"n_layers {cfg.n_layers}; batch {batch}, prompt {prompt_len}, "
        f"gen {gen}; approx {approx} ranks {ranks}")

    # serve_batch's own seed: params, prompts and caches as it makes them
    key = jax.random.PRNGKey(0)
    max_len = prompt_len + gen
    p_sds = jax.eval_shape(lambda: init_tree(param_specs(cfg), key))
    c_sds = jax.eval_shape(
        lambda: init_tree(cache_specs(cfg, batch, max_len), key))
    b_sds = {"tokens": jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)}
    t_sds = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)

    # the Generators serve_batch drives: their own jitted steps compile
    # once here, at the served shapes, and the served calls reuse them
    gens = {name: Generator(cfg, policy=pol, attn_chunk=32, scan_chunk=8)
            for name, pol in (("exact", None), ("approx", policy))}
    dots = {}
    for name, g in gens.items():
        t0 = time.perf_counter()
        lowered = g.prefill.lower(p_sds, b_sds, c_sds)
        pre = lowered.compile()
        dec = g.decode.lower(p_sds, c_sds, t_sds, pos_sds,
                             enc_out=None).compile()
        say(f"lm: {name} prefill+decode compiled in "
            f"{time.perf_counter() - t0:.3f} s")
        dots[name] = lowered.as_text().count("dot_general")
        for step, comp in (("prefill", pre), ("decode", dec)):
            m = comp.memory_analysis()
            need = (m.argument_size_in_bytes + m.output_size_in_bytes
                    + m.temp_size_in_bytes - m.alias_size_in_bytes)
            say(f"lm: {name} {step} memory: arguments "
                f"{m.argument_size_in_bytes} B, outputs "
                f"{m.output_size_in_bytes} B, temporaries "
                f"{m.temp_size_in_bytes} B, aliased "
                f"{m.alias_size_in_bytes} B; needs {need} of {hbm_bytes} B")
            _require(need <= hbm_bytes,
                     f"{name} {step} fits the device memory")
    say(f"lm: dot_general ops in the prefill program: {dots}")
    _require(dots["approx"] > dots["exact"],
             "the approximate policy took the rank-k correction path")

    t0 = time.perf_counter()
    params = jax.block_until_ready(init_tree(param_specs(cfg), key))
    prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)
    say(f"lm: params initialized on device in "
        f"{time.perf_counter() - t0:.3f} s")

    # the user path: serve_batch decodes greedily through the Generator
    served = {}
    for name, g in gens.items():
        t0 = time.perf_counter()
        toks, tps = serve_batch(cfg, batch=batch, prompt_len=prompt_len,
                                gen=gen, policy=g.policy, params=params,
                                prompts=prompts, generator=g)
        toks = np.asarray(toks)
        wall = time.perf_counter() - t0
        _require(toks.shape == (batch, max_len),
                 f"{name} tokens have shape {(batch, max_len)}")
        _require(bool(((toks >= 0) & (toks < cfg.padded_vocab)).all()),
                 f"{name} tokens are in the vocabulary")
        served[name] = toks
        say(f"lm: {name} serve_batch {wall:.3f} s, generate reports "
            f"{tps:.3f} tokens/s")
    agree = float(np.mean(served["exact"][:, prompt_len:]
                          == served["approx"][:, prompt_len:]))
    say(f"lm: approx generated tokens equal to exact: {agree}")

    # the served programs stepped again from the state generate starts
    # from: their argmaxes must be the tokens generate emitted, which ties
    # the logits checked below to the served output
    caches = init_tree(cache_specs(cfg, batch, max_len), key)
    pos = jnp.int32(prompt_len)
    timings, logits = {}, {}
    for name, g in gens.items():
        first = jnp.asarray(served[name][:, prompt_len:prompt_len + 1])
        pre_logits, pre_caches = g.prefill(params, {"tokens": prompts},
                                           caches)
        _, dec_logits, _ = g.decode(params, pre_caches, first, pos,
                                    enc_out=None)
        logits[name] = (np.asarray(pre_logits[:, -1], np.float32),
                        np.asarray(dec_logits[:, -1], np.float32))
        for step, lg, col in (("prefill", logits[name][0], prompt_len),
                              ("decode", logits[name][1], prompt_len + 1)):
            _require(bool(np.isfinite(lg).all()),
                     f"{name} {step} logits are finite")
            _require(bool((lg.argmax(-1) == served[name][:, col]).all()),
                     f"{name} {step} argmax is the token generate emitted")
        # the approximate steps take tens of seconds each on one v5e (the
        # rank-k correction gathers its tables anew every call): time
        # them once
        reps = 3 if name == "exact" else 1
        t_pre = _timed(lambda: g.prefill(params, {"tokens": prompts},
                                         caches), reps)
        t_dec = _timed(lambda: g.decode(params, pre_caches, first, pos,
                                        enc_out=None), reps)
        timings[name] = {"prefill_s": t_pre, "decode_step_s": t_dec}
        say(f"lm: {name} warm prefill {t_pre} s for {batch}x{prompt_len} "
            f"tokens; warm decode step {t_dec} s for {batch} tokens")

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    # row 0's prompt and first generated token: the reference's last two
    # positions are the prefill's and the first decode step's logits
    toks0 = served["exact"][:1, :prompt_len + 1]
    got = np.stack([logits["exact"][0][0], logits["exact"][1][0]])
    t0 = time.perf_counter()
    ref = reference_logits(params, toks0, cfg, last=2)[0]
    t_ref = time.perf_counter() - t0
    errs = {step: rel(got[i], ref[i])
            for i, step in enumerate(("prefill", "decode"))}
    fault = reference_logits(params, toks0, cfg, last=2, rope=False)[0]
    fault_errs = {step: rel(got[i], fault[i])
                  for i, step in enumerate(("prefill", "decode"))}
    say(f"lm: float32 host reference in {t_ref:.3f} s; exact-policy "
        f"logits vs reference, relative L2 error: {errs} (tolerance "
        f"{rtol}); max abs error "
        f"{float(np.max(np.abs(got - ref)))}; argmax equal "
        f"{[int(a) == int(b) for a, b in zip(got.argmax(-1), ref.argmax(-1))]}")
    say(f"lm: planted fault, the reference with RoPE skipped: relative L2 "
        f"error {fault_errs} (must exceed the tolerance {rtol})")
    for step in errs:
        _require(errs[step] <= rtol,
                 f"exact {step} logits within {rtol} of the reference")
        _require(fault_errs[step] > rtol,
                 f"the tolerance rejects a skipped RoPE at {step}")
    approx_shift = rel(logits["approx"][0][0], logits["exact"][0][0])
    say(f"lm: approx-policy prefill logits differ from exact by relative "
        f"L2 {approx_shift}")
    _require(approx_shift > 0.0, "the approximate policy changed the logits")
    return {"timings": timings, "logits_rel_err": errs}


# ---------------------------------------------------------------------------

def main() -> int:
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit("chip_smoke: src/repro not found next to this "
                         "script; run it from a checkout of the repository")
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        # the float32 reference runs on the host's CPU backend; the
        # first platform listed stays JAX's default
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); this script runs only on one")
    sys.path.insert(0, SRC)
    from repro import compile_cache, obs
    from repro.configs import get_config

    obs.setup_logging("warning")
    t_start = time.perf_counter()
    say(f"device: {dev.platform} {dev.device_kind}, count "
        f"{len(jax.devices())}, jax {jax.__version__}; compile cache "
        f"{compile_cache.enable()}")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        t0 = time.perf_counter()
        campaign_phase(workdir, gauss={}, hier=HIER)
        say(f"phase campaign passed in {time.perf_counter() - t0:.3f} s")

    full = get_config("granite-8b")
    cfg = dataclasses.replace(full, n_layers=GRANITE_LAYERS)
    say(f"lm: layer cut {cfg.n_layers} of {full.n_layers}, "
        f"{cfg.param_count()} params")
    t0 = time.perf_counter()
    lm_phase(cfg, batch=LM_BATCH, prompt_len=LM_PROMPT, gen=LM_GEN,
             approx=APPROX, rtol=LOGITS_RTOL,
             hbm_bytes=dev.memory_stats()["bytes_limit"])
    say(f"phase lm passed in {time.perf_counter() - t0:.3f} s; device "
        f"peak_bytes_in_use {dev.memory_stats().get('peak_bytes_in_use')}")
    say(f"total {time.perf_counter() - t_start:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
