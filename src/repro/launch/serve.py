"""Serving launcher: batched generation with an optional AutoQ policy.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \
        --bits 8 --n-new 32

Smoke configs initialize f32 weights; published widths initialize bf16 (a
gemma2-2b f32 tree alone is 12.8 GB).  The initialized tree is handed to
the engine and never bound here, so once the engine has quantized it
nothing keeps the source copy alive beside the quantized one.
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs import get
from repro.data import TokenStream
from repro.launch.compile_cache import enable_compile_cache
from repro.models import LM
from repro.quant.policy import QuantPolicy
from repro.serve import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--bits", type=float, default=0,
                    help="uniform weight QBN (0 = full precision)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--n-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--attn-impl", choices=("pallas", "ref"),
                    default="pallas",
                    help="attention backend (ref = jnp oracle path)")
    ap.add_argument("--kv-bits", type=int, default=0,
                    help="8 = int8 KV cache (dense and paged)")
    args = ap.parse_args()

    spec = get(args.arch)
    cfg = spec.smoke if args.smoke else spec.config
    if cfg.frontend == "audio_stub":
        raise SystemExit("audio_stub archs need frame embeddings; use the "
                         "dry-run for musicgen serving shapes")
    enable_compile_cache()
    model = LM(cfg)
    policy = graph = None
    if args.bits > 0:
        graph = model.graph(seq_len=args.prompt_len, batch=args.batch)
        policy = QuantPolicy.uniform(graph, args.bits)

    dtype = jnp.float32 if args.smoke else jnp.bfloat16
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0), dtype=dtype),
                      policy=policy, graph=graph,
                      max_len=args.prompt_len + args.n_new,
                      attn_impl=args.attn_impl,
                      kv_bits=args.kv_bits or None)
    prompts = TokenStream(vocab=cfg.vocab).batch(
        0, args.batch, args.prompt_len)["tokens"]
    out = eng.generate(prompts, n_new=args.n_new,
                       temperature=args.temperature)
    s = out["stats"]
    print(f"prefill {s.prefill_s*1e3:.1f} ms | decode "
          f"{s.decode_tok_per_s:.1f} tok/s | {s.tokens_out} tokens")
    print("sample:", out["tokens"][0][:24].tolist())


if __name__ == "__main__":
    main()
