"""Headline benchmark: training throughput on the available chip(s).

Prints the device it ran on (``platform=... device_kind=... count=...``),
then ONE json line: {"metric", "value", "unit", "vs_baseline"}.

Round-1 metric: GPT-2 125M training tokens/sec/chip (driver config #1).
``vs_baseline`` reports measured MFU / 0.45 — the north-star is >=45% MFU
(BASELINE.md); >1.0 means the target is beaten on this metric.

Needs a TPU whose ``device_kind`` is in the peak table below; anything else
is an error.  ``JAX_PLATFORMS=cpu`` asks for the toy-size CPU smoke on
purpose (tier-1 contract test): the JSON line then carries a smoke metric
name and ``vs_baseline: null`` — a CPU wall-clock is not a device number.
"""

from __future__ import annotations

import argparse
import json
import os
import time


#: bf16 peak matmul FLOP/s per chip, keyed by a substring of
#: ``device_kind`` (Google Cloud TPU documentation)
PEAK_BF16_FLOPS = {
    "v5e": 197e12, "v5 lite": 197e12, "v5litepod": 197e12,
    "v5p": 459e12, "v4": 275e12, "v3": 123e12, "v6e": 918e12,
}


def peak_flops_per_chip(device) -> float:
    kind = getattr(device, "device_kind", "").lower()
    for k, v in PEAK_BF16_FLOPS.items():
        if k in kind:
            return v
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {device.device_kind!r} "
        f"— add it to PEAK_BF16_FLOPS with its source instead of guessing")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--flash", dest="flash", default=None,
                    action="store_true", help="force the Pallas flash kernel")
    ap.add_argument("--no-flash", dest="flash", action="store_false")
    ap.add_argument("--remat", dest="remat", default=None, action="store_true")
    ap.add_argument("--no-remat", dest="remat", action="store_false")
    ap.add_argument("--micro-bs", type=int, default=None)
    ap.add_argument("--gas", type=int, default=None,
                    help="gradient accumulation steps")
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--bq", type=int, default=None, help="flash block_q")
    ap.add_argument("--bk", type=int, default=None, help="flash block_k")
    ap.add_argument("--remat-policy", default=None,
                    choices=["full", "dots", "dots_flash"])
    ap.add_argument("--multi", type=int, default=None,
                    help="global steps per dispatch (train_batches); "
                         "1 = per-step dispatch")
    ap.add_argument("--no-scan-layers", dest="scan_layers",
                    action="store_false", default=None,
                    help="unroll the layer loop (no scan residual stacking)")
    args = ap.parse_args()

    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.utils import platform

    dev = jax.devices()[0]
    n_chips = len(jax.devices())
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"count={n_chips} jax={jax.__version__}", flush=True)
    on_tpu = platform.on_tpu()  # raises unless TPU, or CPU asked for by name
    if on_tpu:
        platform.enable_compile_cache(
            os.path.dirname(os.path.abspath(__file__)))
        peak = peak_flops_per_chip(dev)
        cfg = gpt2.GPT2Config.gpt2_125m()
        # selective remat with the flash kernel's o pinned, unrolled layer
        # loop (no scan residual-stacking copies), the fused v2 flash
        # backward with 1024-row q blocks, and gas=16 so the
        # optimizer/step overhead amortizes over 16 microbatches
        cfg.remat = True
        cfg.use_flash = True
        cfg.remat_policy = "dots_flash"
        cfg.scan_layers = False
        cfg.flash_block_q, cfg.flash_block_k = 1024, 1024
        micro_bs, seq, steps = 32, 1024, 16
        gas = 16
    else:  # JAX_PLATFORMS=cpu: toy-size contract smoke, no device number
        cfg = gpt2.GPT2Config(vocab_size=2048, max_seq_len=256, num_layers=4,
                              num_heads=8, hidden_size=256)
        micro_bs, seq, steps = 2, 128, 5
        gas = 1
    if args.flash is not None:
        cfg.use_flash = args.flash
    if args.remat is not None:
        cfg.remat = args.remat
    if args.bq:
        cfg.flash_block_q = args.bq
    if args.bk:
        cfg.flash_block_k = args.bk
    if args.remat_policy:
        cfg.remat_policy = args.remat_policy
    if args.scan_layers is not None:
        cfg.scan_layers = args.scan_layers
    if args.seq and args.micro_bs is None and on_tpu:
        # keep tokens/microbatch constant so long sequences fit HBM
        micro_bs = max(1, micro_bs * seq // args.seq)
    micro_bs = args.micro_bs or micro_bs
    seq = args.seq or seq
    steps = args.steps or steps
    cfg.max_seq_len = max(cfg.max_seq_len, seq)

    gas = args.gas or gas
    config = {
        "train_micro_batch_size_per_gpu": micro_bs,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1 if n_chips > 1 else 0},
    }
    model = gpt2.build(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)

    rng = np.random.default_rng(0)

    def batch():
        return {"input_ids": rng.integers(
            0, cfg.vocab_size, size=(engine.train_batch_size(), seq + 1)
        ).astype(np.int32)}

    # warmup / compile (both the single-step and the multi-step programs)
    multi = args.multi if args.multi is not None else \
        (5 if (on_tpu and gas == 1) else 1)
    multi = max(1, min(multi, steps))
    steps -= steps % multi
    for _ in range(2):
        _, m = engine.train_batch(batch())
    if multi > 1:
        _, m = engine.train_batches([batch() for _ in range(multi)])
    # the final loss depends on the whole step chain, so fetching its
    # value (which waits for the device) bounds every step before it
    float(m["loss"])
    t0 = time.perf_counter()
    if multi > 1:
        for _ in range(steps // multi):
            _, m = engine.train_batches([batch() for _ in range(multi)])
    else:
        for _ in range(steps):
            _, m = engine.train_batch(batch())
    float(m["loss"])
    dt = time.perf_counter() - t0

    tokens = engine.train_batch_size() * seq * steps
    tok_per_sec_per_chip = tokens / dt / n_chips
    flops_per_token = 6.0 * cfg.num_params() + 12 * cfg.num_layers * \
        cfg.hidden_size * seq  # attention term
    print(json.dumps({
        "metric": "gpt2_125m_train_tokens_per_sec_per_chip" if on_tpu else
                  "gpt2_smoke_train_tokens_per_sec_per_chip",
        "value": round(tok_per_sec_per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(tok_per_sec_per_chip * flops_per_token / peak
                             / 0.45, 4) if on_tpu else None,
    }))


if __name__ == "__main__":
    main()
