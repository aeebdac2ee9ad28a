"""``prefill_kda_kernel_pct`` on made-up ``ctx``s: 100 where the kernel
scanned every token that counts, 0 on XLA's path, and nothing to read
without the traced window's counters, from the parent (whose prefill calls
add zeros to both counters) and from a program without them."""

import json

import pytest
from test_counter_readers import BENCH, reader

NAME = "prefill_kda_kernel_pct"
# Between the markers: 310 chunks of 256 tokens of which 21 % are padding,
# through six KDA layers.
TOKENS = 6 * int(310 * 256 * 0.79)
COUNTERS = {"attn_rows_read_state_prefill": TOKENS, "attn_rows_dense_state_prefill": TOKENS}


def read(counters):
    return reader(NAME)({"trace": None, "trace_counters": counters, "counters": dict(COUNTERS)})


@pytest.mark.parametrize("scanned, expected", [
    (TOKENS, 100.0),      # every chunk program's scan is the kernel's
    (0, 0.0),             # XLA's path: the gate refused every call
    (TOKENS // 4, 25.0),  # a family of programs of which some were refused
], ids=["kernel", "xla", "mixed"])
def test_the_share_of_the_scanned_tokens_that_the_kernel_scanned(scanned, expected):
    assert read({**COUNTERS, "attn_rows_read_state_prefill": scanned}) == pytest.approx(expected)


@pytest.mark.parametrize("counters", [
    None,                                                                     # --trace 0
    {"attn_rows_read_state_prefill": 0, "attn_rows_dense_state_prefill": 0},  # the parent: zeros
    {"attn_rows_read_state_prefill": TOKENS},                                 # no such counter
    {},
], ids=["untraced", "parent", "no_dense", "neither"])
def test_nothing_to_read(counters):
    assert read(counters) is None


def test_benchmark_json_lists_it_for_the_cell_with_kda_layers():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "step programs", "moves": "ttft_p50_ms",
        "workloads": ["ling-3.0-flash-vl-l7e128.rag-closed"],
    }
