"""The system under test for the `bert` model: what the benchmark takes
from the program to drive a BERT pre-training step through
`Executor.run`. The one file of the pair that imports paddle_tpu."""

import numpy as np


def build(cfg, seq_len):
    """What examples/train_bert.py --flash builds: bf16 AMP around
    Adam, flash attention on, fused kernels and the fused optimizer at
    their defaults. Returns (main, startup, loss)."""
    import paddle_tpu as fluid
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.models import BertConfig, build_bert_pretrain

    bert = BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        ffn_size=cfg["ffn_size"], max_position=cfg["max_position"],
        type_vocab_size=cfg["type_vocab_size"],
        hidden_dropout=cfg["hidden_dropout"],
        attention_dropout=cfg["attention_dropout"],
        initializer_range=cfg["initializer_range"],
        use_flash_attention=bool(cfg["train"]["flash_attention"]))
    opt = decorate(fluid.optimizer.Adam(cfg["train"]["learning_rate"]),
                   init_loss_scaling=1.0, use_dynamic_loss_scaling=False,
                   dest_dtype=cfg["train"]["amp_dtype"])
    main, startup, _feeds, fetches = build_bert_pretrain(bert, seq_len,
                                                         optimizer=opt)
    return main, startup, fetches["loss"]


def moment1_names(main):
    """{parameter: the variable that holds Adam's first moment of it}.
    The optimizer numbers its accumulators per process, so the name is
    looked up, not spelled."""
    out = {}
    for name, var in main.global_block().vars.items():
        owner = getattr(var, "accumulator_owner", None)
        if owner and "_moment1_" in name:
            out[owner] = name
    return out


def make_feeds(rng, n, batch, seq_len, vocab):
    """n host batches; every row full length (packed sequences), so the
    shapes and the work are the same for every seed and the seed draws
    token ids only. Labels are the next token (synthetic_batch's
    shape, copied)."""
    pos = np.tile(np.arange(seq_len, dtype="int64"), (batch, 1))
    mask = np.ones((batch, seq_len), "float32")
    feeds = []
    for _ in range(n):
        src = rng.integers(0, vocab, (batch, seq_len), dtype=np.int64)
        feeds.append({"src_ids": src, "pos_ids": pos,
                      "labels": np.roll(src, -1, axis=1), "input_mask": mask})
    return feeds
