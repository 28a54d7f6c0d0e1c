"""Serving launcher: batched generation with the prefill+decode engine.

  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b \
      --reduced --batch 4 --prompt-len 32 --max-new 16

``--paged`` serves the same prompts through the continuous-batching
:class:`~repro.serve.engine.PagedServeEngine` instead of the static
lockstep path, and the observability flags light up the serve plane:
``--trace-dir`` writes a Perfetto timeline with one async interval per
request (submit -> first_token -> finish) plus prefill/commit/decode
spans, and ``--metrics-jsonl`` appends the registry snapshot (TTFT and
decode-latency histograms, admission rejects, pool utilization) — see
docs/observability.md.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged-KV continuous-batching "
                         "engine (decoder-only models)")
    ap.add_argument("--trace-dir", default=None,
                    help="write trace-<pidx>.json (per-request spans; "
                         "needs --paged)")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append the serve metrics-registry snapshot "
                         "(TTFT/decode histograms) to this file")
    args = ap.parse_args()

    from repro.configs import default_run_config, get_config, \
        reduced as reduce_cfg
    from repro.configs.base import ShapeConfig
    from repro.launch.compile_cache import init_compile_cache
    from repro.models import build_model
    from repro.observability import MetricsRegistry, Tracer, set_tracer
    from repro.serve.engine import PagedServeEngine, ServeEngine

    init_compile_cache()

    tracer = None
    if args.trace_dir:
        tracer = Tracer(process_index=jax.process_index())
        set_tracer(tracer)
    registry = MetricsRegistry()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    run = default_run_config(cfg, ShapeConfig("serve", args.prompt_len,
                                              args.batch, "decode"))
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 4,
        cfg.vocab_size)

    if args.paged:
        eng = PagedServeEngine(model, run, metrics=registry)
        t0 = time.perf_counter()
        for row in range(args.batch):
            eng.submit([int(t) for t in prompts[row]], args.max_new)
        out = eng.serve(params, temperature=args.temperature)
        dt = time.perf_counter() - t0
        ttft = registry["serve_ttft_ms"]
        print(f"[serve] {cfg.name} paged: {args.batch} requests x "
              f"{args.prompt_len} prompt + {args.max_new} new in "
              f"{dt:.2f}s ({args.batch*args.max_new/dt:.1f} tok/s, "
              f"ttft_p50={ttft.quantile(0.5):.1f}ms "
              f"decode_compiles={eng.decode_compiles()})")
        print({rid: toks[:8] for rid, toks in sorted(out.items())})
    else:
        eng = ServeEngine(model, run)
        batch = {"tokens": prompts}
        if cfg.n_image_tokens:
            batch["image_embeds"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(2),
                (args.batch, cfg.n_image_tokens, cfg.d_model))
        if cfg.is_encoder_decoder:
            batch["audio_frames"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(3),
                (args.batch, cfg.n_audio_frames, cfg.d_model))
        t0 = time.perf_counter()
        out = eng.generate(params, batch, max_new=args.max_new,
                           temperature=args.temperature)
        dt = time.perf_counter() - t0
        print(f"[serve] {cfg.name}: {args.batch}x{args.prompt_len} prompt "
              f"+ {args.max_new} new tokens in {dt:.2f}s "
              f"({args.batch*args.max_new/dt:.1f} tok/s)")
        print(out)

    if args.metrics_jsonl:
        registry.write_jsonl(args.metrics_jsonl, extra={"final": True})
        print(f"[metrics] wrote {args.metrics_jsonl}")
    if tracer is not None:
        path = tracer.flush(args.trace_dir)
        print(f"[trace] wrote {path} ({len(tracer)} events, "
              f"{tracer.dropped} dropped)")


if __name__ == "__main__":
    main()
