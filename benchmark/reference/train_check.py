"""The reference side of a training cell's ``correct``, run on the chip once
the trainer has gone: the plain reference follows the same first steps on
the same token ids and weights (both made again from the seed) and reports
each step's loss, the norm of every leaf's first gradient and the norm of
every leaf's change over the steps.

``control`` (calibration only): ``"fp8"`` computes every matrix product of
the reference from operands rounded to float8; ``"half_batch"`` plants the
fault of a step that leaves half of the batch out and takes the mean over
the rest.
"""

from __future__ import annotations

import json
import sys
import time


def reference(spec: dict) -> dict:
    from benchmark.harness import traffic as TR
    from benchmark.reference.train_ref import Reference

    cfg, traffic = spec["config"], spec["traffic"]
    control = spec.get("control")
    ref = Reference(cfg, spec["seed"],
                    precision="fp8" if control == "fp8" else "f32",
                    moment_dtype=cfg["check"]["reference_moment_dtype"])
    steps = cfg["check"]["steps"]
    losses = []
    for k in range(steps):
        tokens = TR.train_batch(spec["seed"], k, traffic["batch"],
                                traffic["seq"], cfg["vocab_size"])
        if control == "half_batch":
            tokens = tokens[:traffic["batch"] // 2]
        losses.append(ref.step(tokens))
    return {"losses": losses, "grad_norms": ref.grad_norms,
            "change_norms": ref.change_norms()}


def main() -> int:
    spec = json.load(open(sys.argv[1]))

    from benchmark.harness import common as C
    from benchmark.harness import jaxenv

    jaxenv.enable_cache()
    compiles = jaxenv.count_compiles()
    device = jaxenv.device_report()
    if device["platform"] != spec["config"]["platform"]:
        C.emit_child_result({"device": device})
        return 3
    t0 = time.time()
    out = reference(spec)
    out.update(device=device, seconds=time.time() - t0,
               peak_bytes=jaxenv.peak_bytes(),
               compiles=[compiles["requests"], compiles["hits"]])
    C.emit_child_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
