"""Serving launcher: batched prefill + decode for any --arch.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --tokens 16

Runs through :class:`repro.serving.engine.ServeEngine`, so the timing
printed here comes from the same telemetry spans every other entry point
records (``docs/OBSERVABILITY.md``): tok/s is every emitted token — the
``prefill`` span's (each prompt's first output token falls out of the
prefill logits) plus the ``decode`` span's — over the combined span
duration, not an ad-hoc stopwatch.  ``--telemetry DIR`` additionally
writes the trace artifacts there.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config, list_archs
from repro.serving.engine import Request, ServeEngine
from repro.telemetry import Telemetry
from repro.utils.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b", choices=list_archs())
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry", default=None, metavar="DIR",
                    help="write trace.json / metrics.json artifacts here")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch) if args.full_config else get_smoke_config(args.arch)
    tel = Telemetry(out_dir=args.telemetry)
    engine = ServeEngine(
        cfg, max_seq=args.prompt_len + args.tokens, seed=args.seed, telemetry=tel
    )
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab_size
    ), np.int32)
    kw = {}
    if cfg.family == "encdec":
        kw["enc_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, cfg.n_audio_frames, cfg.d_model),
            dtype=cfg.param_dtype,
        )
    reqs = [Request(prompt=prompts[i], max_new_tokens=args.tokens)
            for i in range(args.batch)]
    engine.run(reqs, **kw)
    decode = [s for s in tel.tracer.spans if s.name == "decode"][-1]
    prefill = [s for s in tel.tracer.spans if s.name == "prefill"][-1]
    # every emitted token counts: the prefill span holds the first output
    # token per prompt, the decode span the rest — summing both makes the
    # rate exact (and non-zero) even at --tokens 1, where decode is empty
    toks = prefill.attrs.get("tokens", 0) + decode.attrs.get("tokens", 0)
    dur = prefill.duration + decode.duration
    print(f"{cfg.name}: prefill {prefill.duration*1e3:.1f} ms, "
          f"tokens={toks}, {toks/max(dur, 1e-9):.1f} tok/s (CPU)")
    if "flops" in decode.attrs:
        print(f"decode step: {decode.attrs['flops']:.3g} flops, "
              f"{decode.attrs['bytes_moved']:.3g} bytes moved (analytic)")
    print("row 0:", reqs[0].out.tolist())
    if args.telemetry:
        for k, p in tel.flush().items():
            print(f"  wrote {k}: {p}")


if __name__ == "__main__":
    main()
