#!/usr/bin/env python3
"""How often the program's router chooses other experts than the plain
reference's, and what that costs in the number ``correct`` compares.
Not part of a benchmark run; PERF.md gives its readings, and the limits
of the AFMoE cells rest on them.

At the cell's own sizes, weights from the seed: one teacher-forced pass
of the PROGRAM's block (``llama.forward``: bf16, the flash kernel with
its window, the grouped expert matmul) and one of the reference
(float32) over the same token rows, each giving its logits and, through
a spy on the router, its top-k of every routed layer at every position.
Counted: the positions whose top-k SET differs, layer by layer; the gap
(the reference's best logit minus its logit of the program's best
token: what ``kinds/generate.py`` compares for a served token) over all
positions, over those whose sets agree in every layer, and over the
others; and the same gap with the program run AGAIN with its routing
forced to the reference's choices: what is left then is the precision
alone.  Also the load the balanced biases leave: the share of all
choices that falls on the held experts and, of 32 positions drawn at
random, how many distinct held experts a layer sees.

    python3 benchmark/flips_afmoe.py --workload trinity.mixed_ctx_c32 \\
        --seeds 1,2 [--rows 2] [--tokens 8704] [--dry-run]
"""

import argparse
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src", "python"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

import manifest  # noqa: E402
import reference_afmoe as R  # noqa: E402
import weights_afmoe as W  # noqa: E402
from models import afmoe_generate  # noqa: E402


def program_pass(cfg):
    """``(params, tokens [1, T], forced [Lr, T, k], use) -> (logits [T, V],
    chosen [Lr, T, k])``: the program's forward, its router seen and,
    where ``use``, its choices replaced by ``forced``."""
    from tpuserver.models import llama

    def run(params, tokens, forced, use):
        seen = []
        own = llama._route

        def spy(layer, x, m):
            chosen, w = own(layer, x, m)
            seen.append(chosen)
            # the forced experts under the program's own scores: a bias
            # that lifts exactly them above every other
            lift = 1e3 * jax.nn.one_hot(forced[len(seen) - 1], m.n_experts,
                                        dtype=jnp.float32).sum(1)
            forced_chosen, forced_w = own(dict(layer, router_bias=lift), x, m)
            return (jnp.where(use, forced_chosen, chosen),
                    jnp.where(use, forced_w, w))

        llama._route = spy
        try:
            logits = llama.forward(params, tokens, cfg)[0]
        finally:
            llama._route = own
        return logits, jnp.stack(seen)
    return jax.jit(run)


def reference_pass(sizes, seed, tokens):
    """The reference's logits [T, V] and top-k [Lr, T, k] for one token
    row, and the margin between its k-th and (k+1)-th expert."""
    frozen = W.frozen(sizes)
    key = W.root_key(seed)
    biases = R.router_biases(seed, sizes)
    chosen, margins = [], []
    with jax.default_matmul_precision("highest"):
        x = R._embed_fn(frozen)(key, tokens[None])
        for i in range(sizes["num_hidden_layers"]):
            if biases[i] is not None:
                c, m = _choice_fn(frozen, i)(key, x[0], biases[i])
                chosen.append(c)
                margins.append(m)
            x = R._layer_fn(frozen, i, "f32")(key, x, biases[i])
        logits = R._head_fn(frozen, "f32")(key, x)[0]
    return logits, jnp.stack(chosen), jnp.stack(margins)


@functools.lru_cache(maxsize=None)
def _choice_fn(frozen, i):
    sizes = dict(frozen)
    s, window, _ = R._kind(sizes, i)

    def run(key, x, bias):
        w = W.layer(key, sizes, i, jnp.float32)
        y = R.attended(w, x, s, window)[1]
        scores = jax.nn.sigmoid(
            jnp.matmul(y, w["router"], precision=R.HIGHEST))
        top, chosen = lax.top_k(scores + bias, s["top_k"] + 1)
        return chosen[:, :-1], top[:, -2] - top[:, -1]
    return jax.jit(run)


def gap(ref_logits, program_logits):
    """Per position: the reference's best logit minus its logit of the
    program's best token."""
    best = jnp.argmax(program_logits, axis=-1)
    return np.asarray(jnp.max(ref_logits, -1) - jnp.take_along_axis(
        ref_logits, best[:, None], axis=1)[:, 0])


def stat(values):
    return ({"n": int(values.size), "mean": float(values.mean()),
             "max": float(values.max())} if values.size else {"n": 0})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="trinity.mixed_ctx_c32")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--tokens", type=int, default=8704)
    ap.add_argument("--dry-run", action="store_true")
    args = ap.parse_args(argv)

    import tpuserver
    from tpuserver.ops import flash

    tpuserver.enable_compile_cache()
    if args.dry_run:
        flash.set_kernel_mode(interpret=True)
    else:
        tpuserver.require_tpu()
    m = manifest.load_manifest()
    config = manifest.config_of(m, manifest.cell(m, args.workload))[1]
    if args.dry_run:
        config = dict(config, **config["dry_run"])
    entry = config["repository"][0]
    sizes = afmoe_generate.sizes_of(config, entry)
    cfg = afmoe_generate.build(config, entry)._cfg
    run = program_pass(cfg)
    held = np.arange(sizes["expert_first"],
                     sizes["expert_first"] + sizes["num_experts"])
    for seed in (int(s) for s in args.seeds.split(",")):
        params = W.weights(seed, sizes, R.router_biases(seed, sizes))
        rng = np.random.default_rng([seed, 0xF11F])
        for row in range(args.rows):
            tokens = jnp.asarray(rng.integers(
                0, sizes["vocab_size"], (args.tokens,), dtype=np.int32))
            ref_logits, ref_chosen, margins = reference_pass(
                sizes, seed, tokens)
            logits, chosen = run(params, tokens[None], ref_chosen, False)
            forced_logits, _ = run(params, tokens[None], ref_chosen, True)
            a, b = np.sort(np.asarray(chosen), -1), np.sort(
                np.asarray(ref_chosen), -1)
            differs = (a != b).any(-1)                  # [Lr, T]
            any_layer = differs.any(0)
            g, forced = gap(ref_logits, logits), gap(ref_logits, forced_logits)
            picks = np.asarray(ref_chosen)              # [Lr, T, k]
            draws = [rng.choice(args.tokens, min(32, args.tokens), False)
                     for _ in range(200)]
            distinct = np.mean([len(np.intersect1d(picks[layer, d], held))
                                for d in draws for layer in range(len(picks))])
            counts = np.stack([np.bincount(
                p.reshape(-1), minlength=sizes["router_experts"])
                for p in picks])
            print("FLIPS " + json.dumps({
                "seed": seed, "row": row, "positions": args.tokens,
                "differ_by_layer": differs.sum(1).tolist(),
                "differ_any_layer": int(any_layer.sum()),
                "margin_p1_p50": [float(np.percentile(margins, q))
                                  for q in (1, 50)],
                "gap_all": stat(g), "gap_agree": stat(g[~any_layer]),
                "gap_differ": stat(g[any_layer]),
                "gap_forced_to_reference": stat(forced),
                "held_share_of_choices": float(
                    counts[:, held].sum() / counts.sum()),
                "load_max_over_mean": float(
                    (counts.max(1) / counts.mean(1)).mean()),
                "distinct_held_of_32_positions": float(distinct)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
