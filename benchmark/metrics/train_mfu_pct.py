"""Whole train step: operations the forward and backward passes need per
token (6 per matmul parameter and causal attention; recomputation not
counted) times tokens per second per chip, over the chip's peak."""
from benchmark.harness import opsbytes
from benchmark.harness.peaks import peak
from benchmark.metrics import train_tokens_per_s


def read(rec, variant=None):
    per_token = opsbytes.train_flops_per_token(rec["cell"]["config"], rec["cell"]["traffic"]["seq"])
    return 100.0 * per_token * train_tokens_per_s.read(rec) / peak(rec["device"]["kind"])["bf16_flops"]
