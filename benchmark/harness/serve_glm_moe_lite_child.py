"""The serving pod's program as the benchmark starts it for a configuration
of ``kind: serve_glm_moe_lite``: ``serve_afmoe_child.py`` with the
``glm4_moe_lite`` architecture's configuration and parameter tree.

Registers the cell's configuration with ``models/llama.py CONFIGS`` (where
``MODEL_PRESET`` is looked up; the preset's type selects the program's
code), puts the benchmark's seeded weights where the server's smoke-mode
initialiser would put its own, opens ``serve_child``'s control port and
runs ``infer/serve.py main()`` as a pod does.  No file of the program
changes.
"""

from __future__ import annotations

import json
import os
import sys
import threading
from http.server import ThreadingHTTPServer

from benchmark.harness.serve_child import Control


def main() -> int:
    cfg = json.load(open(os.environ["BENCH_CONFIG_FILE"]))
    bench_seed = int(os.environ["BENCH_SEED"])

    import jax

    from paddle_operator_tpu.infer import serve
    from paddle_operator_tpu.models import glm_moe_lite, llama

    from benchmark.harness import glm_moe_lite as H
    from benchmark.harness import jaxenv, weights

    llama.CONFIGS[os.environ["MODEL_PRESET"]] = H.config(
        cfg, cfg["serve"]["max_len"])
    Control.compiles = jaxenv.count_compiles()

    def seeded_params(acfg, ckpt, *, seed: int = 0, mesh=None):
        """Same tree, dtypes and placement as the server's own smoke-mode
        initialiser (``afmoe_serve.load_params``); the values are the
        benchmark's, from ``--seed``, in one jitted call."""
        del ckpt, seed, mesh
        shapes = glm_moe_lite.param_shapes(acfg)
        one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        params = jax.jit(
            lambda k: H.make_tree(k, shapes, acfg),
            out_shardings=jax.tree.map(lambda _: one, shapes))(
                weights.root_key(bench_seed))
        return params, False

    serve.load_serving_params = seeded_params

    ctl = ThreadingHTTPServer(("127.0.0.1", int(os.environ["BENCH_CTL_PORT"])),
                              Control)
    threading.Thread(target=ctl.serve_forever, daemon=True).start()
    return serve.main()


if __name__ == "__main__":
    sys.exit(main())
