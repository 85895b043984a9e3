"""Continuous-batching serving driver with train-to-serve delta streaming.

Requests are admitted in waves (admission control: at most ``--max-batch``
slots per wave, each request with its own generation length), prefilled
together, then decoded token-by-token.  Between decode steps the replica
polls an in-process trainer: every ``--publish-every`` decode steps the
trainer takes a drift step and publishes a compressed weight delta
(``serve/publish.py``), which the replica scatter-adds into the live
serving params (``serve/subscribe.py``) without stopping decode.  Every
``--resync-every``-th publish ships the dense bucket — replica params
equal trainer params exactly at those epochs.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --host-devices 8 --mesh 4x2 --requests 12 --max-batch 8 \
      --prompt-len 64 --gen 16 --publish-every 4 --publish-ratio 0.01

``--publish-every 0`` freezes the weights (pure serving, no trainer).
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8,
                    help="total requests in the synthetic queue")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="admission control: slots per decode wave")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16,
                    help="max generation length; requests draw from "
                    "[gen//2, gen]")
    ap.add_argument("--mesh", default="4x2")
    ap.add_argument("--host-devices", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--publish-every", type=int, default=0,
                    help="trainer publishes a weight delta every N decode "
                    "steps (0 = frozen weights)")
    ap.add_argument("--publish-ratio", type=float, default=0.01,
                    help="density of the delta stream")
    ap.add_argument("--resync-every", type=int, default=8,
                    help="every Nth publish ships the dense bucket")
    args = ap.parse_args(argv)
    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}")

    from repro.launch.env import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.compression import CompressionConfig
    from repro.dist.layout import build_layout
    from repro.launch.mesh import make_mesh
    from repro.models import init_params
    from repro.serve import (RESYNC, apply_resync, init_publisher_state,
                             make_apply_delta, make_decode_step,
                             make_prefill_step, message_bits, publish)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    dims = tuple(int(x) for x in args.mesh.split("x"))
    axes = ("pod", "data", "model")[-len(dims):]
    mesh = make_mesh(dims, axes)

    key = jax.random.PRNGKey(args.seed)
    trainer = init_params(cfg, key)
    params = jax.tree.map(lambda x: x, trainer)  # replica starts in sync
    B, T = args.max_batch, args.prompt_len
    s_max = T + args.gen

    # --- delta stream setup (trainer simulated in-process) -------------
    streaming = args.publish_every > 0
    if streaming:
        pub_config = CompressionConfig(compressor="topk",
                                       ratio=args.publish_ratio)
        layout = build_layout(trainer, 1, pub_config)
        pub_state = init_publisher_state(layout)
        apply_jit = make_apply_delta(layout, mesh, params)
        pub_key = jax.random.fold_in(key, 0x5EEDED)

        @jax.jit
        def drift(p, i):
            # stand-in for a real optimizer step: small deterministic drift
            return jax.tree.map(
                lambda x: x + 1e-3 * jnp.sin(x * (1.0 + 0.1 * i)), p)

    prefill_step = make_prefill_step(cfg, mesh, s_max=s_max)
    decode = jax.jit(make_decode_step(cfg, mesh))

    # --- synthetic request queue ---------------------------------------
    rng = np.random.default_rng(args.seed)
    queue = [int(rng.integers(max(1, args.gen // 2), args.gen + 1))
             for _ in range(args.requests)]
    done = 0
    tokens_out = 0
    slot_steps = slot_busy = 0
    deltas = resyncs = 0
    wire_bits = 0
    decode_steps = 0
    t_start = time.time()

    wave = 0
    while queue:
        admit = queue[:args.max_batch]
        queue = queue[args.max_batch:]
        nact = len(admit)
        gens = admit + [0] * (B - nact)  # padded slots generate nothing
        wave_gen = max(admit)
        key, pk = jax.random.split(key)
        if cfg.frontend == "embeds":
            prompt = jax.random.normal(pk, (B, T, cfg.d_model))
        else:
            prompt = jax.random.randint(pk, (B, T), 0, cfg.vocab_size)
        logits, cache = prefill_step(params, prompt)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        tokens_out += sum(1 for g in gens if g >= 1)
        for i in range(wave_gen - 1):
            if streaming and decode_steps % args.publish_every == 0:
                trainer = drift(trainer, jnp.float32(decode_steps))
                pub_state, msg = publish(pub_state, trainer, layout,
                                         pub_config, pub_key,
                                         resync_every=args.resync_every)
                wire_bits += message_bits(msg)
                if msg.kind == RESYNC:
                    params = apply_resync(params, layout, msg.bucket)
                    resyncs += 1
                else:
                    params = apply_jit(params, msg.values, msg.indices)
                    deltas += 1
            logits, cache = decode(params, cache, jnp.int32(T + i), tok)
            if args.temperature > 0:
                key, sk = jax.random.split(key)
                tok = jax.random.categorical(
                    sk, logits[:, -1] / args.temperature
                ).astype(jnp.int32)[:, None]
            else:
                tok = jnp.argmax(logits[:, -1],
                                 axis=-1).astype(jnp.int32)[:, None]
            decode_steps += 1
            emitted = sum(1 for g in gens if g >= i + 2)
            tokens_out += emitted
            slot_busy += emitted
            slot_steps += B
        done += nact
        wave += 1
    jax.block_until_ready(jax.tree.leaves(params)[0])
    dt = time.time() - t_start

    # staleness gap == the delta-stream residual (publisher invariant)
    if streaming:
        gap = float(jnp.linalg.norm(pub_state["resid"]))
        print(f"stream: {deltas} deltas + {resyncs} resyncs, "
              f"{wire_bits / 8 / 2 ** 20:.3f} MiB on the wire, "
              f"staleness |resid| = {gap:.3e}")
    util = slot_busy / max(1, slot_steps)
    print(f"serve: {done}/{args.requests} requests in {wave} waves, "
          f"{tokens_out} tokens in {dt:.2f}s "
          f"({tokens_out / max(dt, 1e-9):.1f} tok/s), "
          f"slot utilization {util:.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
