#!/usr/bin/env python3
"""``flips_afmoe.py`` for the LFM2-MoE family: how often the program's
router chooses other experts than the plain reference's, and what that
costs in the number ``correct`` compares.  Not part of a benchmark run;
PERF.md gives its readings, and the limits of the LFM2 cell rest on
them.

At the cell's own sizes, weights from the seed: one teacher-forced pass
of the PROGRAM's block (``llama.forward``: bf16, the flash kernel, the
grouped expert matmul, the conv mixers in XLA) and one of the reference
(``reference_lfm2``, float32) over the same token rows, each giving its
logits and its top-k of every routed layer at every position.  Counted
as ``flips_afmoe`` counts them: the positions whose top-k SET differs,
layer by layer; the gap over all positions, over those whose sets agree
in every layer, and over the others; and the gap with the program's
routing forced to the reference's choices: what is left then is the
precision alone.  Also how many distinct experts 32 positions drawn at
random choose in a layer (what a 32-row decode step hits).

    python3 benchmark/flips_lfm2.py --workload lfm2.short_chat_c32 \\
        --seeds 1,2 [--rows 2] [--tokens 1280] [--dry-run]
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
import reference_lfm2 as R  # noqa: E402
import weights_lfm2 as W  # noqa: E402
from flips_afmoe import gap, program_pass, stat  # noqa: E402
from models import lfm2_generate  # noqa: E402


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
                c, m = _choice_fn(frozen, W.kind_of(sizes, i))(
                    key, jnp.int32(i), x[0], biases[i])
                chosen.append(c)
                margins.append(m)
            x = R._run_layer(frozen, sizes, i, "f32", key, x, biases[i])
        logits = R._head_fn(frozen, "f32")(key, x)[0]
    return logits, jnp.stack(chosen), jnp.stack(margins)


@functools.lru_cache(maxsize=None)
def _choice_fn(frozen, kind):
    sizes = dict(frozen)
    s, conv = R.shape_of(sizes), kind[0] == "conv"

    def run(key, i, x, bias):
        w = W.layer(key, sizes, i, jnp.float32, kind=kind)
        y = R.mixed(w, x, s, conv)[1]
        scores = jax.nn.sigmoid(
            jnp.matmul(y, w["router"], precision=R.HIGHEST))
        top, chosen = lax.top_k(scores + bias, s["top_k"] + 1)
        return chosen[:, :-1], top[:, -2] - top[:, -1]
    return jax.jit(run)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="lfm2.short_chat_c32")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--tokens", type=int, default=1280)
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
    sizes = lfm2_generate.sizes_of(config, entry)
    cfg = lfm2_generate.build(config, entry)._cfg
    run = program_pass(cfg)
    for seed in (int(s) for s in args.seeds.split(",")):
        params = W.weights(seed, sizes, R.router_biases(seed, sizes))
        if "dtype" in entry:
            params = jax.tree_util.tree_map(
                lambda leaf: leaf.astype(entry["dtype"]), params)
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
            distinct = np.mean([len(np.unique(picks[layer, d]))
                                for d in draws for layer in range(len(picks))])
            print("FLIPS " + json.dumps({
                "seed": seed, "row": row, "positions": args.tokens,
                "differ_by_layer": differs.sum(1).tolist(),
                "differ_any_layer": int(any_layer.sum()),
                "margin_p1_p50": [float(np.percentile(margins, q))
                                  for q in (1, 50)],
                "gap_all": stat(g), "gap_agree": stat(g[~any_layer]),
                "gap_differ": stat(g[any_layer]),
                "gap_forced_to_reference": stat(forced),
                "distinct_experts_of_32_positions": float(distinct)}),
                flush=True)
        # the next seed's tree does not fit beside this one
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
