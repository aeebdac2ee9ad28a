"""What the files ``tests/test_chip_compile*.py`` share: the described
chip, and the layer-kind configurations' chunk programs compiled for it.

Interpret mode cannot see what Mosaic refuses — a block shape off the
(8, 128) tiling, a dynamic index on a packed sublane, more scoped VMEM
than the limit — so every kernel of the main path is compiled at
published widths for a chip that is described, not attached
(``on-chip-measurement`` guide, section 2).  Nothing runs; a compile
that passes is not a chip run.

The cases are a file a family of programs, so that ``--dist loadfile``
can spread them (as one file they were the longest unit of tier-1's
run, 660-770 s: ROADMAP.md queue 3 item 1).  Each worker that is handed
one of the files loads the TPU's library, inside the ``one_chip``
fixture its file imports from here — never while a module is imported.
``tests/conftest.py`` sets ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` for that (as the
driver's command does): without it, under several workers, a file that
finds the library's lock held by another worker would be skipped by its
fixture, a lower count and nothing red.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# llama3-8b: 32 layers, 32 q / 8 kv heads, head_dim 128.
L, KH, NQ, HD = 32, 8, 32, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it off here.
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # conftest forces "highest" matmul precision for the CPU's sake; the
    # chip runs the default, and Mosaic refuses an fp32-precision bf16 dot.
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_default_matmul_precision", precision_was)
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    """Compile for the described chip; the kernel must be in the program."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding
    )


# (configuration under benchmarks/configs/, rows of the largest group its
# rule allows, the widest window of its slots)
GROUP_PROGRAMS = {
    "mellum": ("mellum2-12b-a2.5b-l12", 4, 8192),
    "ling": ("ling-3.0-flash-vl-l7e128", 8, 2048),
    "exaone": ("k-exaone-236b-a23b-l5e16", 8, 8192),
    "mistral4": ("mistral-small-4-119b-l6e32", 8, 32768),
    "zaya": ("zaya1-8b-l20", 8, 8192),
    "dots3_note": ("dots3-note-prev-l6e32", 8, 16384),
}


# What Mellum's cell has to spare beside its weights, slots and snapshots
# (peak 15.19 of the 16.91 GB the build sees, less the reference check's
# blocks: PERF.md section 4).  K-EXAONE's cut holds 9.09 GB of weights and
# 2.2 GB of state: 5 GB to spare, of which its group of 8 rows of 6,144
# (a full layer's scores of 64 heads are 537 MB a row) may take half.
SPARE_BYTES = 1_400_000_000


# Mistral-Small-4's cut holds 10.85 GB of weights and 2.42 GB of latent
# rows: 3.6 GB to spare.  Its group of 8 rows reads each row's blocks from
# the state in place (no window is gathered), a block of 1,024 keys at a
# time: the largest temporaries are a block's float32 scores (32 heads x
# 256 x 1,024: 33.6 MB) and the experts' combine.
# ZAYA1's cut holds 9.38 GB of weights and 5.37 GB of K/V rows: 2.1 GB to
# spare.  Its group of 8 rows gathers each row's window of 256-wide rows (8
# query heads' scores are 67 MB a row at 8,192); the compiler here counts
# 0.26 GB for the chunks alone.
# dots3-note-prev's cut holds 10.02 GB of weights and 1.27 GB of state:
# 5.6 GB to spare.  Its group of 8 rows reads each row's latent rows and
# index keys in place, a block of 1,024 at a time (a block's float32 scores
# of 128 heads x 256 queries are 134 MB, its expansion 67 MB; a row's
# index scores and their ordered bits 16.8 MB each); the compiler here
# counts 0.63 GB.
SPARE_BY_FAMILY = {
    "exaone": 2_500_000_000, "mistral4": 400_000_000, "zaya": 800_000_000,
    "dots3_note": 1_000_000_000,
}


_CHUNK_PROGRAMS: dict = {}  # what ``_chunk_program`` compiled, by what it was asked


def _chunk_program(one_chip, config: str, rows: int, window: int):
    """``_prefill_suffix_rows`` of a layer-kind configuration under
    benchmarks/configs/, compiled for ``rows`` chunks under ``window``
    against the cell's own slot state.  Returns (compiled, serving, engine).
    A program is compiled once for the tests that read it (half a minute
    each), apart by what the attention gates believe of the platform."""
    from generativeaiexamples_tpu.ops import gqa_decode, kda

    key = (config, rows, window, gqa_decode.platform_of(None), kda.platform_of(None))
    if key not in _CHUNK_PROGRAMS:
        _CHUNK_PROGRAMS[key] = _compile_chunk_program(one_chip, config, rows, window)
    return _CHUNK_PROGRAMS[key]


def _compile_chunk_program(one_chip, config: str, rows: int, window: int):
    import json
    from pathlib import Path

    from generativeaiexamples_tpu.engine.scheduler import make_prefill_suffix_rows
    from generativeaiexamples_tpu.engine.serving_models import HybridServing
    from generativeaiexamples_tpu.models import hybrid

    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / f"{config}.json").read_text())
    engine = model["engine"]
    max_len, chunk = int(engine["max_len"]), int(engine["prefill_chunk_tokens"])
    cfg = hybrid.from_hf_config(
        model, max_len=max_len, kv_dtype=engine["kv_dtype"], draft=engine.get("draft", ""),
    )
    serving = HybridServing(cfg, None, max_len)

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    spec = _spec(one_chip)
    ints, floats = spec((rows,), jnp.int32), spec((rows,), jnp.float32)
    compiled = make_prefill_suffix_rows(serving).lower(
        described(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))),
        described(lambda: hybrid.init_state(cfg, int(engine["max_batch"]), max_len)),
        spec((rows, chunk), jnp.int32), ints, ints, ints,
        spec((2,), jnp.uint32), (floats, floats, ints), window,
    ).compile()
    return compiled, serving, engine


def _no_window_sized_temporaries(
    text: str, *, slots: int, rows: int, window: int, H: int = 32, width: int = 384
) -> None:
    """Nothing of a window's size is made in a program of the latent
    family: no scores of heads x queries x window, no expansion of a
    window through ``W_kvb`` (window x heads x 192), and the slots' state
    (slots, window, 384) is a parameter, scattered into and handed on, but
    never copied, nor is a group's window of it gathered."""
    s = 256
    assert not re.search(rf"(?:f32|bf16)\[(?:\d+,)?{H},{s},{window}\]", text)
    assert not re.search(rf"bf16\[(?:\d+,)?{window},{H},(?:192|64|128)\]", text)
    assert not re.search(rf"bf16\[(?:\d+,)?{window},{H * 192}\]", text)
    assert not re.search(rf"= bf16\[{slots},{window},{width}\]\S* copy\(", text)
    assert not re.search(rf"= bf16\[{rows},{window},{width}\]", text)


# Ling's KDA layers (``ops/kda.py::kda_step_rows``): 32 slots of 32 heads,
# K = V 128, a float32 state of 67.1 MB a layer.
KDA_STATE = (32, 32, 128, 128)


def _state_is_the_kernels_alone(text: str, *, calls: int) -> None:
    """Every operation of a compiled program that makes an array of the
    KDA state's shape is the step kernel, a parameter or a renaming of one:
    no copy, and no XLA fusion that walks the leaf."""
    shape = ",".join(map(str, KDA_STATE))
    made = re.findall(rf"= (?:\([^=]*)?f32\[{shape}\]\S*(?:, [^=]*\))? ([\w-]+)\(", text)
    assert made.count("custom-call") >= calls, made
    assert set(made) <= {"custom-call", "parameter", "get-tuple-element", "bitcast", "tuple", "while"}, made
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*kda_step_rows", text)) >= calls


def _append_leaves_are_the_kernels_alone(text: str, shape: tuple) -> None:
    """Every operation of a compiled program's layer body (the computation
    that holds the decode kernel) that makes or takes an array of an append
    leaf's shape, values ``shape`` int8 or scales ``shape[:-1]`` bfloat16,
    is the kernel, the body's parameter or root, or a renaming between
    them: no ``dynamic-update-slice``, no ``copy`` or ``copy-start`` into
    another memory and back, no ``slice-start`` and no XLA fusion."""
    values = "s8[" + ",".join(map(str, shape)) + "]"
    scales = "bf16[" + ",".join(map(str, shape[:-1])) + "]"
    bodies = [
        c for c in re.split(r"\n(?=(?:ENTRY )?%?[\w.\-]+ \(.*\) -> .* \{\n)", text)
        if re.search(r"custom_call_target=\"tpu_custom_call\"[^\n]*decode_gqa_attention", c)
    ]
    assert len(bodies) == 1, len(bodies)
    touching = [
        re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = .*? ([\w\-]+)\(", line).group(1)
        for line in bodies[0].splitlines()[1:]
        if (values in line or scales in line) and " = " in line
    ]
    assert touching.count("custom-call") == 1, touching
    assert set(touching) <= {"custom-call", "parameter", "get-tuple-element", "bitcast", "tuple"}, touching
