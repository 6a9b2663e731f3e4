"""Whole serving step: matrix-product operations of every token the window
processed (prompts whose first token fell in it, and answer tokens emitted
in it), over the window's seconds and the chip's peak."""
from benchmark.harness import opsbytes
from benchmark.harness.loadgen import tokens_in_window
from benchmark.harness.peaks import peak


def read(rec, variant=None):
    w = rec["window"]
    prompt = sum(len(r.prompt) for r in rec["requests"]
                 if r.token_times and w["t_open"] <= r.token_times[0] < w["t_close"])
    tokens = prompt + tokens_in_window(rec["requests"], w["t_open"], w["t_close"])
    flops = opsbytes.serve_flops_per_token(rec["cell"]["config"]) * tokens
    return 100.0 * flops / w["seconds"] / peak(rec["device"]["kind"])["bf16_flops"] / rec["cell"]["chips"]
