"""Batched serving driver: prefill + decode with optional TERNARY weights —
the paper's deployed-inference path (§III: "at inference stage, only the
quantized model is needed for prediction").

With ``--ternary`` the deployment artifact is built through the
``repro.comm.wire`` codec: the model is compressed to the ternary wire
format, SERIALIZED, and decoded back before serving — so the reported
download size is the measured edge-checkpoint byte count and the served
weights provably round-tripped the wire.

``--packed`` additionally serves ZERO-COPY: the decoded ternary records are
repacked byte-wise into the ``(K//4, N)`` layout ``kernels.ternary_matmul``
consumes, and every weight matmul runs through the Pallas kernel. No
unpacked int8 codes and no dense fp32 weight copy are ever materialized on
the deploy path — weight HBM traffic is 16× below fp32, which is the whole
game for memory-bound decode. ``--residual-codec fp16`` downcasts the
non-quantizable leaves (biases, norms) on the wire as well.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduced \
        --batch 4 --prompt-len 32 --gen 16 --ternary --packed
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import obs
from repro.comm import ChannelConfig, ClientLink, decode_update, encode_update
from repro.core import CodecSpec, FTTQConfig, decompress_pytree
from repro.core import compression as comp
from repro.kernels.repack import packed_params_from_wire
from repro.launch.env import configure_compile_cache
from repro.models.transformer import (
    decode_step, forward, init_cache, init_params, param_count,
)


def ternary_deploy(
    params,
    cfg: FTTQConfig,
    *,
    packed: bool = False,
    residual: str = "none",
    link: ClientLink | None = None,
    loss_rate: float = 0.0,
):
    """Compress → serialize → decode the deployment artifact.

    Returns (served_params, blob, est_download_s, link): ``blob`` is the
    serialized artifact, ``len(blob)`` the edge-checkpoint size. With
    ``packed=False`` the artifact dequantizes to dense arrays (reference
    path); with ``packed=True`` ternary records repack straight into the
    ``(K//4, N)`` kernel layout and stay 2-bit in HBM. ``loss_rate`` runs
    the download estimate through the lossy channel model (chunk loss +
    retransmission), the same scenario knob the federated servers use.
    """
    spec = CodecSpec(kind="ternary", residual=residual, fttq=cfg)
    wire_tree, _ = comp.compress_pytree(params, spec)
    blob = encode_update(wire_tree)
    decoded = decode_update(blob)
    if packed:
        served = packed_params_from_wire(decoded)
    else:
        served = decompress_pytree(decoded)
    if link is None:
        c = ChannelConfig()
        link = ClientLink(0, c.mean_bandwidth_bytes_s, c.base_latency_s, 1.0)
    if loss_rate > 0.0:
        from repro.comm import Channel

        chan = Channel(
            ChannelConfig(latency_jitter_s=0.0, loss_rate=loss_rate,
                          chunk_bytes=4096),
            1, seed=0,
        )
        chan.links[0] = link   # meter over THIS link, not a fresh draw
        return served, blob, chan.transfer(0, len(blob), "down"), link
    return served, blob, link.transfer_time(len(blob)), link


def packed_logits_gap(cfg, served, blob: bytes, tokens) -> tuple[float, float]:
    """Correctness receipt of the packed deploy: (max |Δ| between the
    logits of the packed-kernel ``served`` params and of the dense
    reference decoded from the same wire ``blob``, max |reference logit|).
    The reference is a second dense copy of the model, so drop the fp32
    tree before calling this. Both forwards run at full f32 matmul
    precision: at a TPU's default precision the dense side alone would
    round its operands to bf16, and the gap would measure that."""
    ref = decompress_pytree(decode_update(blob))
    with jax.default_matmul_precision("highest"):
        lp, _, _ = forward(cfg, served, tokens)
        lr, _, _ = forward(cfg, ref, tokens)
    return (float(jnp.max(jnp.abs(lp - lr))), float(jnp.max(jnp.abs(lr))))


def generate(cfg, params, prompts, gen: int, vision=None) -> jax.Array:
    """Prefill ``prompts`` into a KV cache, then ``gen − 1`` greedy decode
    steps; returns the (B, gen) generated tokens, on the device."""
    with obs.span("repro.serve.generate"):
        b, s = prompts.shape
        cache = init_cache(cfg, b, s + gen)
        with obs.span("repro.serve.prefill"):
            logits, cache, _ = forward(cfg, params, prompts, vision_embeds=vision,
                                       cache=cache, pos=0)
            jax.block_until_ready(logits)

        @jax.jit
        def step(params, tok, cache, pos):
            return decode_step(cfg, params, tok, cache, pos, vision_embeds=vision)

        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out = [tok]
        for i in range(gen - 1):
            with obs.span("repro.serve.step"):
                logits, cache = step(params, tok, cache, s + i)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            obs.count("serve.steps")
            out.append(tok)
        jax.block_until_ready(tok)
        return jnp.concatenate(out, axis=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduced", action="store_true",
                    help="the arch's smoke-size config instead of its "
                         "published widths")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--ternary", action="store_true")
    ap.add_argument("--packed", action="store_true",
                    help="serve through kernels.ternary_matmul on the packed "
                         "2-bit layout (requires --ternary)")
    ap.add_argument("--residual-codec", default="none",
                    choices=["none", "fp16", "bf16", "topk"],
                    help="codec for the non-quantizable wire leaves")
    ap.add_argument("--loss-rate", type=float, default=0.0,
                    help="edge-link packet loss for the download estimate "
                         "(chunk retransmission through comm.channel)")
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    if args.packed and not args.ternary:
        raise SystemExit("--packed requires --ternary")
    configure_compile_cache()

    from repro.configs import get_config, get_reduced

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if not cfg.causal:
        raise SystemExit(f"{args.arch} is encoder-only — no decode path")
    if args.packed and cfg.family not in ("dense", "vlm", "audio"):
        raise SystemExit(
            f"--packed serves attention+mlp weights; family {cfg.family!r} "
            "routes its hot matmuls elsewhere (moe/ssm) — use --ternary alone"
        )
    params = init_params(cfg, jax.random.PRNGKey(0))
    print(f"serving {cfg.name}: {param_count(cfg) / 1e6:.1f}M params, "
          f"ternary={args.ternary} packed={args.packed}")
    if args.ternary:
        fp_bytes = len(encode_update(params))
        served, blob, dl_s, link = ternary_deploy(
            params, FTTQConfig(), packed=args.packed,
            residual=args.residual_codec, loss_rate=args.loss_rate,
        )
        params = served     # the fp32 tree is dropped here
        print(f"edge checkpoint: {len(blob) / 1e6:.2f} MB on the wire "
              f"(fp32 {fp_bytes / 1e6:.2f} MB, {fp_bytes / len(blob):.1f}× "
              f"smaller), est. download {dl_s:.1f}s "
              f"@ {link.bandwidth_bytes_s / 1e6:.1f} MB/s")
        if args.packed:
            probe = jax.random.randint(
                jax.random.PRNGKey(9), (2, 8), 0, cfg.vocab_size)
            diff, scale = packed_logits_gap(cfg, params, blob, probe)
            print(f"packed-vs-dequant logits: max |Δ| = {diff:.2e} "
                  f"(max |logit| {scale:.2e})")

    b, s = args.batch, args.prompt_len
    prompts = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size)
    vision = (jax.random.normal(jax.random.PRNGKey(2),
                                (b, cfg.n_patches, cfg.d_model)) * 0.02
              if cfg.family == "vlm" else None)
    t0 = time.perf_counter()
    gen = generate(cfg, params, prompts, args.gen, vision).block_until_ready()
    print(f"generated {b}×{args.gen} tokens in {(time.perf_counter() - t0) * 1e3:.0f} ms "
          "(prefill, decode and the step's compile)")
    print("sample tokens:", gen[0, :12].tolist())


if __name__ == "__main__":
    main()
