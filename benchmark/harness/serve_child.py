"""The serving pod's program as the benchmark starts it.

Registers the cell's configuration with ``models/llama.py CONFIGS`` so that
``MODEL_PRESET`` finds it, puts the benchmark's seeded weights where the
server's smoke-mode initialiser would put its own (that initialiser knows
only seed 0), opens a small control port (memory, compile counts, the
profiler: only the process that holds the chip can trace it) and then runs
``infer/serve.py main()`` as a pod does.  No file of the program changes.
"""

from __future__ import annotations

import json
import os
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse


class Control(BaseHTTPRequestHandler):
    compiles: dict = {}           # jaxenv.count_compiles(), set by main()

    def log_message(self, *a):
        pass

    def do_GET(self):
        import jax

        from benchmark.harness import jaxenv

        url = urlparse(self.path)
        q = parse_qs(url.query)
        if url.path == "/mem":
            out = [d.memory_stats() or {} for d in jax.devices()]
        elif url.path == "/compiles":
            out = dict(self.compiles)
        elif url.path == "/device":
            out = jaxenv.device_report()
        elif url.path == "/trace/start":
            jaxenv.start_trace(q["dir"][0])
            out = {"tracing": True}
        elif url.path == "/trace/stop":
            jax.profiler.stop_trace()
            out = {"tracing": False}
        else:
            self.send_response(404)
            self.end_headers()
            return
        body = json.dumps(out).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def main() -> int:
    cfg = json.load(open(os.environ["BENCH_CONFIG_FILE"]))
    bench_seed = int(os.environ["BENCH_SEED"])

    import jax

    from paddle_operator_tpu.infer import serve
    from paddle_operator_tpu.models import llama

    from benchmark.harness import jaxenv, weights

    llama.CONFIGS[os.environ["MODEL_PRESET"]] = jaxenv.llama_config(
        cfg, cfg["serve"]["max_len"])
    Control.compiles = jaxenv.count_compiles()

    def seeded_params(lcfg, ckpt, *, seed: int = 0, mesh=None):
        """Same tree, dtype and placement as the server's own smoke-mode
        initialiser (``load_serving_params`` without a checkpoint, whose
        `seed` the entry point never sets); the values are the benchmark's,
        from ``--seed``, in one jitted call."""
        del ckpt, seed, mesh
        import jax.numpy as jnp

        from paddle_operator_tpu.infer.quant import serving_params

        model = llama.Llama(lcfg)
        shapes = jax.eval_shape(
            lambda r: serving_params(
                model.init(r, jnp.zeros((1, 8), jnp.int32))["params"],
                lcfg.dtype), jax.random.PRNGKey(0))
        one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        params = jax.jit(
            lambda k: weights.make_tree(k, shapes),
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
