"""Operations and bytes one decode step of a hybrid layer pattern needs
(Mamba-2 and attention mixers, each layer with routed experts and a
shared expert, as ``references/granite_hybrid.py`` computes it), from
the configuration file's keys.

The need is what an ideal step must do, not what the program does:

- every weight held here read once, whatever the batch: the mixers, the
  router, the shared expert, the norms and the tied embedding as the
  output head;
- of the held experts, those the step's rows can reach: a step of ``B``
  rows makes ``B * top_k`` choices, so at most that many experts;
- each row's SSM state and conv window read and written once, and its
  embedding row read;
- each row's cached keys and values of the attention layers only, up to
  its own context (its new token included).

FLOPs count the matrix products (two per multiply-add): the mixers, the
router, the shared expert and the output head for every row, the held
experts for the share of a row's ``top_k`` choices that fall on them
(``top_k * held / router_experts``: routing spread evenly), the SSM
recurrence (decay, update and read-out of each state element) and the
attention of each row over its context.
"""

from __future__ import annotations

import numpy as np

from bench.cost import BYTES, StepCost


class HybridStepCost(StepCost):
    def __init__(self, cfg: dict):
        L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
        kinds = cfg["layer_types"][:L]
        n_attn = sum(1 for k in kinds if k == "attention")
        n_mamba = L - n_attn
        H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        D = cfg.get("head_dim") or d // H
        V, w = cfg["vocab_size"], BYTES[cfg["torch_dtype"]]
        inner = cfg["mamba_expand"] * d
        P, N = cfg["mamba_d_head"], cfg["mamba_d_state"]
        Hm, GN = inner // P, cfg["mamba_n_groups"] * N
        conv = inner + 2 * GN
        E_all, self.held = cfg["router_experts"], cfg["num_local_experts"]
        self.top_k = cfg["num_experts_per_tok"]
        F, Fs = cfg["intermediate_size"], cfg["shared_intermediate_size"]

        mamba = d * (2 * inner + 2 * GN + Hm) + inner * d
        attn = d * (H + 2 * K) * D + H * D * d
        shared = d * E_all + 3 * d * Fs               # router and shared
        self.expert_params = L * 3 * d * F             # one held expert
        self.expert_bytes = w * self.expert_params
        #: matrix parameters every row multiplies by
        self.row_params = (n_mamba * mamba + n_attn * attn + L * shared
                           + d * V)
        small = (n_mamba * (cfg["mamba_d_conv"] * conv + conv + 3 * Hm
                            + inner) + (2 * L + 1) * d)
        self.weight_bytes = w * (self.row_params + small)
        self.row_bytes = w * d                          # one embedding row
        self.state_bytes = n_mamba * (4 * Hm * P * N
                                      + w * (cfg["mamba_d_conv"] - 1) * conv)
        self.kv_token_bytes = w * n_attn * 2 * K * D
        self.attn_flops_per_pos = 4 * n_attn * H * D
        self.row_flops = (2 * self.row_params
                          + 2 * self.expert_params * self.top_k
                          * self.held / E_all
                          + n_mamba * (6 * Hm * P * N
                                       + 2 * cfg["mamba_d_conv"] * conv))

    def step(self, contexts) -> "tuple[float, float]":
        """(FLOPs, bytes) of one step whose rows attend over
        ``contexts`` positions each (their new token included)."""
        ctx = np.asarray(contexts, np.float64)
        B = ctx.size
        reached = min(self.held, B * self.top_k)
        flops = B * self.row_flops + self.attn_flops_per_pos * ctx.sum()
        nbytes = (self.weight_bytes + reached * self.expert_bytes
                  + B * (self.row_bytes + 2 * self.state_bytes)
                  + self.kv_token_bytes * ctx.sum())
        return flops, nbytes
