"""Operations and bytes one decode step needs, from the configuration
file's sizes, and the chip's peaks by ``device_kind``.

The need is what an ideal step must do, not what the program does: every
weight matrix read once per step whatever the batch, plus each live row's
cached keys and values up to its own context length (not the whole
``max_context`` table the program gathers), at the model's stated
widths (``head_dim`` unpadded).  FLOPs count the matrix products (two per
multiply-add) and the attention of each row over its live context.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str, path=PEAKS) -> dict:
    """The chip's peaks; a kind the table does not hold is an error."""
    table = json.loads(pathlib.Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; add them with their source")
    return table[device_kind]


class StepCost:
    def __init__(self, cfg: dict):
        L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
        H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        D = cfg.get("head_dim") or d // H
        F, V = cfg["intermediate_size"], cfg["vocab_size"]
        w = BYTES[cfg["torch_dtype"]]
        self.matmul_params = L * (d * (H + 2 * K) * D + H * D * d
                                 + 3 * d * F) + d * V
        norms = (2 * L + 1) * d * (2 if cfg["norm_type"] == "layernorm"
                                   else 1)
        self.weight_bytes = w * (self.matmul_params + norms)
        self.row_bytes = w * d                  # one embedding row
        self.kv_token_bytes = w * L * 2 * K * D
        self.attn_flops_per_pos = 4 * L * H * D

    def step(self, contexts) -> "tuple[float, float]":
        """(FLOPs, bytes) of one step whose rows attend over
        ``contexts`` positions each (their new token included)."""
        ctx = np.asarray(contexts, np.float64)
        flops = ctx.size * 2.0 * self.matmul_params \
            + self.attn_flops_per_pos * ctx.sum()
        nbytes = self.weight_bytes + ctx.size * self.row_bytes \
            + self.kv_token_bytes * ctx.sum()
        return flops, nbytes

    @staticmethod
    def least_s(flops: float, nbytes: float, peak: dict) -> float:
        """The roofline: the larger of the compute and the memory bound."""
        return max(flops / peak["bf16_flops_per_s"],
                   nbytes / peak["hbm_bytes_per_s"])
