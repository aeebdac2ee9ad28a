"""Share of the state-space block scan's work in prefill programs that was
tokens that count: d ``attn_rows_ssm_tokens_prefill`` / (``chunk_size`` x d
``attn_rows_ssm_blocks_prefill``)
(``models/hybrid.py::_mamba_mixer`` counts both in a prefill call: the
tokens that count, and the blocks ``ops/ssm.py::ssm_scan`` computed over
every row of the call, a group program's pad rows among them).  The rest
is padding: suffixes of 64-256 tokens after a prefix hit in chunks of 256,
and the pad rows of a group.  Higher is better; nothing to read from a
program without the mamba kind's counters."""

from counter_lib import ratio


def read(ctx):
    return ratio(
        ctx, ["attn_rows_ssm_tokens_prefill"], ["attn_rows_ssm_blocks_prefill"],
        100.0 / float(ctx["model"]["chunk_size"]),
    )
