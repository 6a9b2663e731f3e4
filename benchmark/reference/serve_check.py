"""The comparison behind a serving cell's ``correct``, run on the chip once
the server has gone: the plain float32 reference, once over each sampled
prompt with the tokens the timed server answered, layer by layer.

For every served token: how far its reference logit lies below the
reference's best logit at that position.  Greedy tokens only.  The widest
such gap over all the sampled tokens is the number compared.

With ``control`` set (``"fp8"``; calibration only) the same positions are
also computed in the lower precision, and the gap read is that of the token
the lower precision puts first.
"""

from __future__ import annotations

import json
import sys
import time


def main() -> int:
    spec = json.load(open(sys.argv[1]))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import common as C
    from benchmark.harness import jaxenv
    from benchmark.harness import weights as W
    from benchmark.reference import llama_ref as R

    jaxenv.enable_cache()
    compiles = jaxenv.count_compiles()
    device = jaxenv.device_report()
    cfg = R.with_head_dim(json.load(open(spec["config_file"])))
    if device["platform"] != cfg["platform"]:
        C.emit_child_result({"device": device, "gap_max": None, "tokens": 0,
                             "seconds": 0.0})
        return 3
    t0 = time.time()
    reqs = spec["requests"]
    control = spec.get("control")
    out = {"device": device, "gap_max": 0.0, "tokens": 0, "control": None}
    if reqs:
        out.update(compare(cfg, spec["seed"], reqs, control, jax, jnp, np,
                           W, R))
    out["seconds"] = time.time() - t0
    out["compiles"] = [compiles["requests"], compiles["hits"]]
    C.emit_child_result(out)
    return 0


def compare(cfg, seed, reqs, control, jax, jnp, np, W, R) -> dict:
    key = W.root_key(seed)
    n_layers = cfg["num_hidden_layers"]
    n_ans = max(len(r["served"]) for r in reqs)
    n_ans = -(-n_ans // 512) * 512      # one shape whatever the sample
    # two lengths whatever the seed (each a compiled program): the first
    # request, the longest, at max_len; the others at short_tokens
    lengths = [cfg["serve"]["max_len"]] + \
        [cfg["check"]["short_tokens"]] * (len(reqs) - 1)
    seqs = []
    for r, s_pad in zip(reqs, lengths):
        seq = r["prompt"] + r["served"]
        if len(seq) > s_pad:
            raise ValueError(f"a sampled request of {len(seq)} tokens does "
                             f"not fit the {s_pad} it is checked at")
        ids = np.zeros(s_pad, np.int32)
        ids[:len(seq)] = seq
        p, a = len(r["prompt"]), len(r["served"])
        pos = np.zeros(n_ans, np.int32)
        pos[:a] = np.arange(p - 1, p + a - 1)   # logits that predict a token
        tok = np.zeros(n_ans, np.int32)
        tok[:a] = r["served"]
        seqs.append({"ids": ids, "pos": pos, "tok": tok, "n": a})

    # the seed's key is an argument of every compiled piece, never a constant
    # inside one: a program that held it would compile anew for every seed
    @jax.jit
    def make_layer(key, l):
        return R.layer_weights(cfg, key, l)

    def run(precision: str):
        # causal attention: the zero padding behind a sequence never reaches
        # the positions before it
        apply = jax.jit(lambda w, x: R.layer(cfg, w, x, precision))

        @jax.jit
        def logits_at(key, x, pos):
            fs = R.top_weight(cfg, key, "final_norm/scale")
            lm = R.top_weight(cfg, key, "lm_head/kernel")
            return R.head(cfg, fs, lm, x[pos], precision)

        embed = jax.jit(
            lambda key: R.top_weight(cfg, key, "tok_embed/embedding"))(key)
        xs = [embed[jnp.asarray(q["ids"])] for q in seqs]
        del embed
        for l in range(n_layers):
            w = make_layer(key, jnp.int32(l))
            xs = [apply(w, x) for x in xs]
        return [logits_at(key, x, jnp.asarray(q["pos"]))
                for x, q in zip(xs, seqs)]

    def gaps_of(ref, pick):
        out = []
        for lg, pk, q in zip(ref, pick, seqs):
            chosen = jnp.take_along_axis(lg, pk[:, None], -1)[:, 0]
            out.append(float(np.asarray(lg.max(-1) - chosen)[:q["n"]].max()))
        return out

    ref = run("f32")                              # per request [n_ans, vocab]
    by_request = gaps_of(ref, [jnp.asarray(q["tok"]) for q in seqs])
    out = {"gap_max": max(by_request), "gap_by_request": by_request,
           "tokens": sum(q["n"] for q in seqs),
           "padded_lengths": [len(q["ids"]) for q in seqs]}
    if control:
        low = run(control)
        out["control"] = {"name": "ref:" + control, "gap_max": max(
            gaps_of(ref, [lg.argmax(-1) for lg in low]))}
    return out


if __name__ == "__main__":
    sys.exit(main())
