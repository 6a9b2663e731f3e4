"""Whole serving step of a latent-attention sparse-expert model:
matrix-product operations of every token the window processed (prompts
whose first token fell in it, answer tokens emitted in it) at 2 x the
ACTIVE matrix parameters a token
(``opsbytes_glm_moe_lite.active_matmul_params``: the five latent
projections, the router, the shared and the selected experts, the head),
over the window's seconds and the chip's peak."""
from benchmark.harness import opsbytes_glm_moe_lite as O
from benchmark.harness.loadgen import tokens_in_window
from benchmark.harness.peaks import peak


def read(rec, variant=None):
    cfg, w = rec["cell"]["config"], rec["window"]
    if "kv_lora_rank" not in cfg or "n_routed_experts" not in cfg:
        return None
    prompt = sum(len(r.prompt) for r in rec["requests"]
                 if r.token_times and w["t_open"] <= r.token_times[0] < w["t_close"])
    tokens = prompt + tokens_in_window(rec["requests"], w["t_open"], w["t_close"])
    flops = O.serve_flops_per_token(cfg) * tokens
    return 100.0 * flops / w["seconds"] / peak(rec["device"]["kind"])["bf16_flops"] / rec["cell"]["chips"]
