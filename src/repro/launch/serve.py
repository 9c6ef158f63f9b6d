"""Production serving driver: batched prefill + decode with the sharded
KV cache layout of the decode_32k / long_500k cells.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-34b --reduced \
        --batch 4 --prompt-len 32 --gen-len 32

``--private-head`` keeps the transformer trunk local but routes every
decode step's lm-head matmul (``hidden @ W_head``) through the CMPC
serving engine: the head matrix stays the layer owner's private
operand, each step's hidden states are a request against it, and the
reported latencies are the engine's simulated protocol time.  Decoder
families only (dense / moe / vlm), and practical with ``--reduced``.
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from ..compile_cache import setup_compile_cache
from ..configs import SHAPES, get_config, reduced as reduce_cfg
from ..models import build_model
from .mesh import describe, make_elastic_mesh, make_mesh
from .steps import build_decode_step, build_prefill_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mesh", default="elastic")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument(
        "--private-head", action="store_true",
        help="run each decode step's lm-head matmul under CMPC via the "
        "serving engine (decoder families only)",
    )
    ap.add_argument(
        "--workers", type=int, default=16,
        help="simulated edge pool size for --private-head",
    )
    args = ap.parse_args()
    setup_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    model = build_model(cfg)
    if args.mesh == "elastic":
        mesh = make_elastic_mesh()
    else:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
    print(f"serving {args.arch} on {describe(mesh)}")

    max_len = args.prompt_len + args.gen_len
    shape = dataclasses.replace(
        SHAPES["decode_32k"], seq_len=max_len, global_batch=args.batch
    )
    pre_shape = dataclasses.replace(
        SHAPES["prefill_32k"], seq_len=args.prompt_len, global_batch=args.batch
    )
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)).astype(np.int32)

    with mesh:
        params = model.init(jax.random.PRNGKey(0))
        cache = model.init_cache(args.batch, max_len)
        prefill = jax.jit(model.prefill)
        decode = jax.jit(model.decode_step)

        t0 = time.time()
        batch = {"tokens": prompts}
        if cfg.family == "encdec":
            batch = {
                "frames": rng.normal(size=(args.batch, args.prompt_len, cfg.d_model)).astype(np.float32),
                "tokens": prompts[:, :1],
            }
        logits, cache = prefill(params, batch, cache)
        jax.block_until_ready(logits)
        t_pre = time.time() - t0

        tok = np.asarray(jnp_argmax(logits, cfg.vocab_size))
        t0 = time.time()
        if args.private_head:
            steps, report, worst = _decode_private_head(
                args, cfg, model, params, cache, tok
            )
        else:
            steps = 0
            for i in range(args.gen_len - 1):
                pos = np.full((args.batch, 1), args.prompt_len + i, np.int32)
                logits, cache = decode(params, tok[:, None], cache, pos)
                tok = np.asarray(jnp_argmax(logits, cfg.vocab_size))
                steps += 1
            jax.block_until_ready(logits)
        dt = time.time() - t0
    print(f"prefill: {t_pre * 1e3:.1f} ms for {args.prompt_len} x {args.batch} tokens")
    print(f"decode : {dt / max(steps,1) * 1e3:.2f} ms/step (batch {args.batch})")
    if args.private_head:
        s = report.summary()
        print(
            f"private head: {s['replays']} protocol replays over {steps} steps "
            f"on {args.workers} workers, sim latency p50 {s['p50_latency']:.3f}s "
            f"p95 {s['p95_latency']:.3f}s, max |logit err| {worst:.3e}"
        )


def _decode_private_head(args, cfg, model, params, cache, tok):
    """Greedy decode with every step's lm-head matmul served by the
    CMPC engine.  Rows / head columns / the contraction dim are
    zero-padded up to the construction's divisibility (s | k, t | rows,
    t | out); zero padding contributes zero in the field, so the sliced
    logits are the exact fixed-point head product."""
    from ..core.constructions import PlanConfig
    from ..runtime.pool import ShiftedExponential, sample_trace
    from ..serve import ServingEngine

    if model.hidden_step is None or model.head_matrix is None:
        raise SystemExit(
            "--private-head needs a decoder family with a split lm head; "
            f"family {cfg.family!r} does not expose one"
        )
    step = jax.jit(model.hidden_step)
    w = np.asarray(model.head_matrix(params), np.float64)  # [d_model, vocab]
    plan_cfg = PlanConfig()
    k, vocab = w.shape
    pad_k = (-k) % plan_cfg.s
    pad_out = (-vocab) % plan_cfg.t
    pad_rows = (-args.batch) % plan_cfg.t
    traces = [
        sample_trace(
            args.workers, ShiftedExponential(0.1, 0.5), seed=s, net_scale=0.3
        )
        for s in range(4)
    ]
    engine = ServingEngine(
        np.pad(w, ((0, pad_k), (0, pad_out))), traces, plan_cfg, seed=0
    )
    arrival, worst, steps = 0.0, 0.0, 0
    for i in range(args.gen_len - 1):
        pos = np.full((args.batch, 1), args.prompt_len + i, np.int32)
        hidden, cache = step(params, tok[:, None], cache, pos)
        x = np.asarray(hidden[:, -1, :], np.float64)
        # The next head matmul cannot be requested before the previous
        # token is known: arrivals chain on completions.
        req = engine.submit(np.pad(x, ((0, pad_rows), (0, pad_k))), arrival)
        engine.run()
        if req.y is None:
            raise SystemExit(
                f"step {i}: request shed ({req.shed_reason}); a pool of "
                f"{args.workers} workers cannot serve the head — raise --workers"
            )
        logits = req.y[: args.batch, :vocab]
        worst = max(worst, float(np.abs(logits - x @ w).max()))
        tok = logits.argmax(-1).astype(np.int32)
        arrival = req.completion
        steps += 1
    return steps, engine.report(), worst


def jnp_argmax(logits, vocab):
    import jax.numpy as jnp

    return jnp.argmax(logits[:, -1, :vocab], axis=-1).astype(jnp.int32)


if __name__ == "__main__":
    main()
