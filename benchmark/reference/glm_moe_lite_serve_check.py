"""The comparison behind a ``serve_glm_moe_lite`` cell's ``correct``, run on
the chip once the server has gone: ``afmoe_serve_check.py``'s scheme (its
``compare``, imported: the plain float32 reference once over each sampled
prompt with the tokens the timed server answered, layer by layer, one
compiled piece a kind of layer; the MEAN over the served tokens of how far
a token's reference logit lies below the reference's best, ``gap_mean``)
over this architecture's reference (``glm_moe_lite_ref.py``: the EXPANDED
form only, so the served path's absorbed decode steps, its latent pool and
its flash insert are all compared with mathematics they do not share).

Why the mean here too: selecting 4 of 64 experts is as discontinuous as 8
of 128 — bf16 against float32 flips the last expert of a few tokens in a
hundred (PERF.md section 2 has this cell's readings).

With ``control`` set (``"fp8"`` or ``"bf16"``; calibration only) the same
positions are also computed in that precision, as there.
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
    from benchmark.reference import glm_moe_lite_ref as R
    from benchmark.reference.afmoe_serve_check import compare

    jaxenv.enable_cache()
    compiles = jaxenv.count_compiles()
    device = jaxenv.device_report()
    cfg = json.load(open(spec["config_file"]))
    if device["platform"] != cfg["platform"]:
        C.emit_child_result({"device": device, "gap_mean": None,
                             "gap_max": None, "tokens": 0, "seconds": 0.0})
        return 3
    # what ``compare`` asks a configuration for, under afmoe's names: the
    # number of leading dense layers, and a kind of attention a layer
    # (here one kind)
    cfg = dict(cfg, num_dense_layers=cfg["first_k_dense_replace"],
               layer_types=["latent"] * cfg["num_hidden_layers"])
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


if __name__ == "__main__":
    sys.exit(main())
