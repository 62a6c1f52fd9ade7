"""model_type ``held_experts`` — a FIXTURE of the writer's tests: a
table only, of what no program here loads yet and a later type's table
has to say — a chip's SHARE of an expert layer. The configuration
states the experts held here (``n_routed_experts``, reduced) beside the
published count the router still scores (``n_routed_experts_published``)
and the first id held (``expert_id_base``); the router is as wide as
the published count, an F32 vector rides beside it, and the expert ids
do not start at 0. Layer 0 is dense (``first_k_dense_replace``).
"""

ATTENTION_KERNELS = ()
forward_hidden = decode_weight_bytes = kv_bytes_per_token = None


def tensors(config: dict) -> list:
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    n_layers, out = config["num_hidden_layers"], []
    held = range(config["expert_id_base"],
                 config["expert_id_base"] + config["n_routed_experts"])
    for i in range(n_layers):
        lp = f"model.layers.{i}."
        out.append((i, lp + "self_attn.kv_a_layernorm.weight",
                    (config["kv_lora_rank"],), "BF16", "ones"))
        if i < config["first_k_dense_replace"]:
            out.append((i, lp + "mlp.down_proj.weight",
                        (d, config["intermediate_size"]), "BF16", "matrix"))
            continue
        out += [(i, lp + "mlp.gate.weight",
                 (config["n_routed_experts_published"], d), "BF16", "matrix"),
                (i, lp + "mlp.gate.e_score_correction_bias",
                 (config["n_routed_experts_published"],), "F32", "zeros"),
                (i, lp + "mlp.gate.scale", (d,), "F32", "matrix")]
        out += [(i, lp + f"mlp.experts.{e}.down_proj.weight", (d, f), "BF16",
                 "matrix") for e in held]
    return out + [(n_layers, "model.embed_tokens.weight",
                   (config["vocab_size"], d), "BF16", "embed")]
