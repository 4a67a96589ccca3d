"""Operations a token needs in a training step of a model with latent
attention and routed experts (the ``moonlight`` reference's model), for
``mfu.train_moe``.

6 per weight a token passes through, forward and backward: each layer's
attention projections (``W_q``, ``W_kva``, ``W_kvb``, ``W_o``), the
leading dense layers' MLP, each expert layer's router, shared expert and
the routed experts at ``top_k * held / n_experts`` experts a token (the
share of its assignments that lands on this chip's experts), and the
head; embedding lookups are not matrix products.  Plus causal attention:
q.k over ``Dqk`` and p.v over ``Dv`` for ``(S + 1) / 2`` keys on average,
two operations a product, times 3 for forward and backward.
Recomputation is not counted.
"""


def flops_per_token(m: dict, seq: int) -> float:
    d, H, V = m["d_model"], m["n_heads"], m["vocab_size"]
    a, e = m["mla"], m["moe"]
    r, nope, rope, dv = (a["kv_lora_rank"], a["qk_nope_head_dim"],
                         a["qk_rope_head_dim"], a["v_head_dim"])
    attn_w = (d * H * (nope + rope) + d * (r + rope) + r * H * (nope + dv)
              + H * dv * d)
    f = e["d_expert"]
    n_moe = m["n_layers"] - m["n_dense_layers"]
    routed = e["top_k"] * e["experts_held"] / e["n_experts"]
    moe_w = d * e["n_experts"] + 3 * d * f * (routed + e["n_shared_experts"])
    n = (m["n_layers"] * attn_w + m["n_dense_layers"] * 3 * d * m["d_ff"]
         + n_moe * moe_w + d * V)
    attn = 3 * m["n_layers"] * 2 * H * (nope + rope + dv) * (seq + 1) / 2
    return 6 * n + attn
