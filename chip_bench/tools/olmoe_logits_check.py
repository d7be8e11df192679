"""Logits of the program's OLMoE model against the plain reference's, on the
device that is attached, at the configuration's published widths.

    python3 chip_bench/tools/olmoe_logits_check.py [--seed N] [--last 256] [--out FILE]

One seeded sequence of ``sequence_length`` tokens on seeded weights; the last
``--last`` positions of the program's bf16 forward pass against the
reference's float32 one (``configs/olmoe-1b-7b_reference.py``), and beside
it the reference computed in bf16 throughout (the nearest precision below the
configuration's, which keeps norms, rotary positions, router and softmax in
fp32: what the tolerance has to tell apart from a faithful program).  Prints one JSON line, for each of the two: the median, 90th
percentile and maximum over positions of the largest absolute difference of a
position's logits, the share of positions far above the median, how many
routed slots went to another expert than in the reference, and the whole
sequence's next-token loss against the reference's.  It exits non-zero unless
the program is within ``LIMITS``.  A tool, run once per builder
session; nothing of the benchmark's result line comes from here.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# What the program has to stay within, against the float32 reference, on
# seeded weights at the published widths (PERF.md section 6, PR 27, has the
# readings these were set from).  Logits of the last positions: the program
# hands back bf16 logits, whose own rounding at the largest logit (4.8:
# 2**-8 * 4.8 = 0.019, the worst of 50,304 of them a position) sets the
# median of 0.04; the precision below shares it, so the logits alone cannot
# tell the two apart.  A token whose 8th and 9th probabilities lie closer
# than the bf16 residual stream resolves goes to another expert than in the
# reference and its logits differ by tenths: such positions are counted
# (3-7% of them on fresh weights, in either precision), not held to the
# median's limit.  The loss over the whole sequence is where the
# configuration's fp32 shows: taken in fp32 from the program's logits it
# follows the reference to a few 1e-5 (the moved tokens average out); taken
# in bf16, which resolves 0.06 at ln(vocab) = 10.8, it is off by 1e-3.
LIMITS = {"median": 0.08, "share_over_10x_median": 0.15, "loss_rel": 2e-4}


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=2 ** 31 + 27)
    p.add_argument("--last", type=int, default=256)
    p.add_argument("--out", default=None)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from chip_bench import reference, spec
    from horovod_tpu.parallel.moe import publish_routing

    cell = spec.Cell("olmoe-1b-7b-wfbp-1chip")
    sizes = cell.sizes
    module = cell.config_module()
    config = module.Config(sizes)
    ref = module._load_reference()
    dev = jax.devices()[0]
    params, _ = jax.jit(config.init)(
        jax.device_put(reference.seed_key(args.seed), dev))
    tokens = jax.jit(config.make_batch)(
        jax.device_put(reference.rank_key(args.seed, 0), dev))["tokens"][:1]
    last = args.last

    def next_token_loss(logits, dtype):
        """Mean -log p(next token) over the whole sequence, in ``dtype``."""
        logp = jax.nn.log_softmax(logits[:, :-1].astype(dtype), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))

    def program(p, t):
        from horovod_tpu.models.transformer import moe_stats

        logits, state = config.model.apply({"params": p}, t, mutable=["moe"])
        return (logits[:, -last:].astype(jnp.float32),
                moe_stats(state["moe"]).tokens_per_expert[:, 0],
                next_token_loss(logits, jnp.float32))

    def exact(p, t):
        with jax.default_matmul_precision("highest"):
            x, _, _, counts = ref.hidden_states(p, t, sizes)
            logits = x @ p["lm_head"]["kernel"]
            return (logits[:, -last:], counts,
                    next_token_loss(logits, jnp.float32))

    def lower_precision(p, t):
        # The nearest precision below the configuration's: bf16 everywhere,
        # also where the configuration keeps fp32 (norms, rotary positions,
        # router, softmax of attention, the loss).
        p = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), p)
        x, _, _, counts = ref.hidden_states(p, t, sizes)
        logits = x @ p["lm_head"]["kernel"]
        return (logits[:, -last:].astype(jnp.float32), counts,
                next_token_loss(logits, jnp.bfloat16))

    want, want_counts, want_loss = jax.jit(exact)(params, tokens)
    ours = jax.jit(program)(params, tokens)

    def against_reference(outputs):
        """The largest absolute difference of each of the last positions'
        logits, its median, 90th percentile and maximum over them; the share
        of them over ten times the median (a token sent to another expert);
        the routed slots that went to another expert (at least: from the
        counts); the relative difference of the whole sequence's loss; and
        whether all of that is within LIMITS."""
        got, counts, loss = outputs
        ranked = jnp.sort(jnp.max(jnp.abs(got - want), axis=-1).reshape(-1))
        median = float(ranked[ranked.size // 2])
        r = {"median": median, "p90": float(ranked[(9 * ranked.size) // 10]),
             "max": float(ranked[-1]),
             "share_over_10x_median": float(jnp.mean(ranked > 10 * median)),
             "rms": float(jnp.sqrt(jnp.mean((got - want) ** 2))),
             "routed_slots_moved": int(jnp.sum(jnp.abs(
                 counts - want_counts))) // 2,
             "loss": float(loss),
             "loss_rel": abs(float(loss) - float(want_loss))
             / float(want_loss)}
        r["within_limits"] = all(r[k] <= v for k, v in LIMITS.items())
        return r

    out = {"device": dev.device_kind, "seed": args.seed, "positions": last,
           "routed_slots": int(jnp.sum(want_counts)),
           "max_abs_logit": float(jnp.max(jnp.abs(want))),
           "rms_logit": float(jnp.sqrt(jnp.mean(want ** 2))),
           "reference_loss": float(want_loss), "limits": LIMITS,
           # The program's router on this one sequence, through the helper a
           # user calls outside the step (it sets the registry's gauges).
           "max_load_ratio": publish_routing(
               {"tokens_per_expert": ours[1], "steps": 1})["max_load_ratio"],
           "program_vs_reference": against_reference(ours),
           "bf16_reference_vs_reference": against_reference(
               jax.jit(lower_precision)(params, tokens))}
    # The precision below is there to show what the limits refuse; bf16
    # resolves 0.06 at this loss, so one sequence in about fourteen lands
    # within 2e-4 by chance, and the exit code does not depend on it.
    out["ok"] = out["program_vs_reference"]["within_limits"]
    print(json.dumps(out), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
