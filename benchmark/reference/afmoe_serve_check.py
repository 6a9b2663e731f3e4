"""The comparison behind a ``serve_afmoe`` cell's ``correct``, run on the
chip once the server has gone: ``serve_check.py`` for the ``afmoe``
architecture.  The plain float32 reference (``afmoe_ref.py``) runs once
over each sampled prompt with the tokens the timed server answered, layer
by layer (a layer's kind — dense or expert feed-forward, sliding or full
attention — is static, so each kind is one compiled piece).

For every served token: how far its reference logit lies below the
reference's best logit at that position (greedy tokens; 0 where the served
token is the reference's own).  The number compared is the MEAN of that gap
over all the sampled tokens (``gap_mean``); the widest (``gap_max``, what
the dense cells compare) and the share of tokens that are not the
reference's own are reported beside it.  Why the mean: selecting 8 of 128
experts is discontinuous, bf16 against float32 flips the eighth expert of a
few tokens in a hundred, a flipped token's logits move by tenths, and the
widest gap of a sound run (0.6-1.0) then reads nearly what the fp8
control's does (1.2-1.3), while the mean differs several times over
(PERF.md section 2).  A request is checked at ``check.short_tokens`` if it
fits, else at ``serve.max_len``.

With ``control`` set (``"fp8"`` or ``"bf16"``; calibration only) the same
positions are also computed in that precision: the gaps read are those of
the token it puts first, and ``flipped_share`` is the share of the
reference's (token, expert) selections it does not make.
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
    from benchmark.reference import afmoe_ref as R

    jaxenv.enable_cache()
    compiles = jaxenv.count_compiles()
    device = jaxenv.device_report()
    cfg = json.load(open(spec["config_file"]))
    if device["platform"] != cfg["platform"]:
        C.emit_child_result({"device": device, "gap_mean": None,
                             "gap_max": None, "tokens": 0, "seconds": 0.0})
        return 3
    t0 = time.time()
    out = {"device": device, "gap_mean": 0.0, "gap_max": 0.0, "tokens": 0,
           "control": None}
    if spec["requests"]:
        out.update(compare(cfg, spec["seed"], spec["requests"],
                           spec.get("control"), jax, jnp, np, W, R))
    out["seconds"] = time.time() - t0
    out["compiles"] = [compiles["requests"], compiles["hits"]]
    C.emit_child_result(out)
    return 0


def compare(cfg, seed, reqs, control, jax, jnp, np, W, R) -> dict:
    key = W.root_key(seed)
    n_layers, n_dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    n_ans = max(len(r["served"]) for r in reqs)
    n_ans = -(-n_ans // 512) * 512      # one shape whatever the sample
    short, full = cfg["check"]["short_tokens"], cfg["serve"]["max_len"]
    seqs = []
    for r in reqs:
        seq = r["prompt"] + r["served"]
        s_pad = short if len(seq) <= short else full
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
        seqs.append({"ids": ids, "pos": pos, "tok": tok, "n": a,
                     "len": len(seq)})

    # the seed's key and the layer's index are arguments of every compiled
    # piece, never constants inside one: a piece that held them would
    # compile anew for every seed and every layer.  Dense and expert layers
    # have other leaves, so each kind makes its weights in its own piece.
    def leaves_of(layer_kind: int):
        return R.layer_leaves(cfg, 0 if layer_kind == 0 else n_dense)

    def make_weights(names):
        def make(key, l):
            return {n: W.make_leaf(key, "layers/" + n, shape,
                                   R.leaf_dtype(cfg, n), l).astype(jnp.float32)
                    for n, shape in names.items()}
        return jax.jit(make)

    makers = {kind: make_weights(leaves_of(kind)) for kind in (0, 1)}

    def run(precision: str):
        """Per request the logits at its answer positions ``[n_ans, V]``,
        and per expert layer the experts every position selected."""
        # causal attention: the zero padding behind a sequence never reaches
        # the positions before it.  One compiled block per (dense or expert,
        # sliding or full, padded length): static arguments of ``R.layer``.
        applies: dict = {}

        def apply(l, w, x):
            kind = (l < n_dense, cfg["layer_types"][l])
            if kind not in applies:
                applies[kind] = jax.jit(lambda w, x, l=l: R.layer(
                    cfg, w, x, l, precision, with_routing=True))
            return applies[kind](w, x)

        @jax.jit
        def logits_at(key, x, pos):
            fs = R.top_weight(cfg, key, "final_norm/scale")
            lm = R.top_weight(cfg, key, "lm_head/kernel")
            return R.head(cfg, fs, lm, x[pos], precision)

        table = jax.jit(
            lambda key: R.top_weight(cfg, key, "tok_embed/embedding"))(key)
        xs = [R.embed(cfg, table, jnp.asarray(q["ids"])) for q in seqs]
        del table
        routed = [[] for _ in seqs]
        for l in range(n_layers):
            w = makers[0 if l < n_dense else 1](key, jnp.int32(l))
            for i, x in enumerate(xs):
                xs[i], idx = apply(l, w, x)
                if idx is not None:
                    routed[i].append(np.asarray(idx[:seqs[i]["len"]]))
            del w
        return [logits_at(key, x, jnp.asarray(q["pos"]))
                for x, q in zip(xs, seqs)], routed

    def gaps_of(ref, pick):
        """All the sampled tokens' gaps, request by request."""
        out = []
        for lg, pk, q in zip(ref, pick, seqs):
            chosen = jnp.take_along_axis(lg, pk[:, None], -1)[:, 0]
            out.append(np.asarray(lg.max(-1) - chosen)[:q["n"]])
        return out

    def stats(gaps) -> dict:
        flat = np.concatenate(gaps)
        return {"gap_mean": float(flat.mean()), "gap_max": float(flat.max()),
                "mismatch_share": float((flat > 0).mean())}

    ref, ref_routed = run("f32")
    gaps = gaps_of(ref, [jnp.asarray(q["tok"]) for q in seqs])
    out = {**stats(gaps), "gap_max_by_request": [float(g.max()) for g in gaps],
           "gap_mean_by_request": [float(g.mean()) for g in gaps],
           "tokens": sum(q["n"] for q in seqs),
           "padded_lengths": [len(q["ids"]) for q in seqs]}
    if control:
        low, low_routed = run(control)
        same = total = 0
        for a_layers, b_layers in zip(ref_routed, low_routed):
            for a, b in zip(a_layers, b_layers):
                same += int((a[:, :, None] == b[:, None, :]).any(-1).sum())
                total += a.size
        out["control"] = {
            "name": "ref:" + control,
            **stats(gaps_of(ref, [lg.argmax(-1) for lg in low])),
            "flipped_share": 1.0 - same / max(total, 1)}
    return out


if __name__ == "__main__":
    sys.exit(main())
