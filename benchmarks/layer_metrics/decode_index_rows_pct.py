"""Bytes of index keys and selected latent rows the full layers' decode
steps read, over the bytes of every latent row the decoding slots hold:
(d ``attn_rows_read_index_decode`` x an index key + d
``attn_rows_read_selected_decode`` x a stored latent row) / (d
``attn_rows_seen_latent_decode`` x a stored latent row), the row widths
from the configuration (``index_head_dim``; ``kv_lora_rank`` +
``qk_rope_head_dim`` in whole lanes of 128, as the program stores them).
``models/hybrid.py::_mla_mixer`` counts what a step READ: every slot's
index keys up to the step's window and ``index_topk`` gathered rows a slot,
whoever decodes, so few decoding rows among many slots read over 100.
Lower is better; nothing to read from a program without the counters."""

ITEM = {"float32": 4}


def read(ctx):
    c = ctx.get("trace_counters")
    names = ("attn_rows_read_index_decode", "attn_rows_read_selected_decode",
             "attn_rows_seen_latent_decode")
    if c is None or any(n not in c for n in names) or not c[names[2]]:
        return None
    model, item = ctx["model"], ITEM.get(ctx["engine"]["kv_dtype"], 2)
    row = -(-(int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"])) // 128) * 128 * item
    key = int(model["index_head_dim"]) * item
    return 100.0 * (c[names[0]] * key + c[names[1]] * row) / (c[names[2]] * row)
