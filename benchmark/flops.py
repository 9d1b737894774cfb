"""Operations the algorithm needs, from shapes. Matrix products only
(2 FLOPs a multiply-add); layer norms, softmax, GELU, embeddings'
gathers and the optimizer are left out, as is anything recomputed."""


def encoder_matmul_params(cfg):
    """Weights of one transformer layer's four matrix products."""
    h, f = cfg["hidden_size"], cfg["ffn_size"]
    return 4 * h * h + 2 * h * f


def bert_train_flops_per_token(cfg, seq_len):
    """Forward + backward of the pre-training step, per token:
    6 * (layers' weights + the LM head's) for the weight products, and
    12 * L * d * S for attention's two products (QK^T and PV: 4*S*d
    forward a layer, three times that with the backward)."""
    weights = (cfg["num_layers"] * encoder_matmul_params(cfg)
               + cfg["hidden_size"] * cfg["vocab_size"])
    attention = 12 * cfg["num_layers"] * cfg["hidden_size"] * seq_len
    return 6 * weights + attention


def gpt_forward_flops(cfg, tokens_processed, tokens_emitted, context_sum):
    """Forward passes of a served decoder: every token processed goes
    through the layers' weight products (2 * N_layers), every token
    emitted through the head (2 * d * V), and a token at cached length
    c pays 4 * L * d * c in attention; `context_sum` is the sum of c
    over the tokens processed."""
    body = 2 * cfg["num_layers"] * encoder_matmul_params(cfg)
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    attention = 4 * cfg["num_layers"] * cfg["hidden_size"]
    return (body * tokens_processed + head * tokens_emitted
            + attention * context_sum)
