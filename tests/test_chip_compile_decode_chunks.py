"""The layer-kind families' decode chunks at the cells' widths and slot
state, compiled (or lowered) for the described v5e
(``tests/chip_compile_lib.py``).
"""

import re

import jax
import jax.numpy as jnp
import pytest

from chip_compile_lib import (  # noqa: F401 — ``one_chip`` is the file's fixture
    GROUP_PROGRAMS,
    KDA_STATE,
    SPARE_BY_FAMILY,
    _spec,
    _state_is_the_kernels_alone,
    one_chip,
)


# Rows of a window layer's ring (``test_chip_compile_chunk_attention.py``
# holds the chunk programs to the same).
RING_ROWS = {"mellum": 1024, "exaone": 128}


@pytest.mark.parametrize("family", sorted(RING_ROWS))
def test_a_decode_chunk_keeps_the_rings_wide_form(one_chip, family, monkeypatch):
    """Mellum's and K-EXAONE's decode chunk (8 steps over 32 slots, one
    query a row or a token and its draft), lowered with the gates believing
    they are on the chip: the full layers walk their rows, and a window
    layer is ``gqa.attend_ring``'s wide form, as before the ring kernel:
    its name is nowhere in a decode step."""
    import json
    from pathlib import Path

    from generativeaiexamples_tpu.engine.serving_models import HybridServing
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.ops import dispatch, gqa_decode, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / f"{GROUP_PROGRAMS[family][0]}.json").read_text())
    engine = model["engine"]
    max_len, b, steps = int(engine["max_len"]), int(engine["max_batch"]), int(engine["decode_chunk_size"])
    cfg = hybrid.from_hf_config(
        model, max_len=max_len, kv_dtype=engine["kv_dtype"], draft=engine.get("draft", "")
    )
    serving = HybridServing(cfg, None, max_len)

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    spec = _spec(one_chip)
    ints, floats, flags = spec((b,), jnp.int32), spec((b,), jnp.float32), spec((b,), jnp.bool_)
    drafting = (spec((1, b), jnp.int32), flags, ints, flags) if cfg.draft else ()
    dispatch.TAKEN.clear()
    text = serving.make_decode_chunk().lower(
        described(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))),
        described(lambda: hybrid.init_state(cfg, b, max_len)),
        ints, ints, spec((2,), jnp.uint32), floats, floats, ints, steps, max_len, flags,
        *drafting,
    ).as_text()
    assert "gqa_rows_decode_attention" in text and "gqa_ring_chunk_attention" not in text
    ring = RING_ROWS[family]
    s = 2 if cfg.draft else 1
    assert dispatch.TAKEN[f"attn_window b={b} s={s} t={ring}"] == "xla"
    assert not any("attn_window_chunk" in site for site in dispatch.TAKEN)


def test_the_verify_chunk_compiles_at_the_published_widths(one_chip, monkeypatch):
    """The decode chunk of a model that drafts its own step
    (``HybridServing._make_verify_chunk``: the prediction module's
    catch-up, then 8 steps of the stack over [token, draft], acceptance,
    the module over the accepted positions) for k-exaone-236b-a23b-l5e16's
    32 slots of 8,192 at the widest decode window: the grouped products
    and the full layers' row walk are in it, it returns tokens (8, 32, 2)
    with a count a row and each row's newest token and length for the
    chunk behind it, and its
    temporaries stay under what the cell has to spare beside 9.09 GB of
    weights and 2.2 GB of state."""
    import json
    from pathlib import Path

    from generativeaiexamples_tpu.engine.serving_models import HybridServing
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.ops import gqa_decode, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / "k-exaone-236b-a23b-l5e16.json").read_text())
    engine = model["engine"]
    max_len, b, steps = int(engine["max_len"]), int(engine["max_batch"]), int(engine["decode_chunk_size"])
    cfg = hybrid.from_hf_config(model, max_len=max_len, kv_dtype=engine["kv_dtype"], draft=engine["draft"])
    assert cfg.draft == "mtp" and cfg.qk_norm and cfg.rope_full.rope_type == "none"
    serving = HybridServing(cfg, None, max_len)

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    spec = _spec(one_chip)
    ints, floats = spec((b,), jnp.int32), spec((b,), jnp.float32)
    compiled = serving.make_decode_chunk().lower(
        described(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))),
        described(lambda: hybrid.init_state(cfg, b, max_len)),
        ints, ints, spec((2,), jnp.uint32), floats, floats, ints, steps, max_len,
        spec((b,), jnp.bool_), spec((1, b), jnp.int32), spec((b,), jnp.bool_),
        ints, spec((b,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The full layers' attention is the row walk (the stack's and the
    # module's, with one query a row in the catch-up), and the scatter that
    # writes a step's rows feeds it in place: no K or V leaf is copied.
    assert text.count("gqa_rows_decode_attention") >= 3
    assert not re.search(r"= bf16\[32,8192,1024\]\S* copy\(", text)
    _, toks, counts, (newest, lengths), aux = compiled.out_info
    assert toks.shape == (steps, b, 2) and counts.shape == (steps, b)
    assert newest.shape == (1, b) and lengths.shape == (b,)
    assert aux.shape == (len(serving.counter_names),)
    print("verify chunk temporaries", compiled.memory_analysis().temp_size_in_bytes)
    assert compiled.memory_analysis().temp_size_in_bytes < SPARE_BY_FAMILY["exaone"]


def test_the_verify_chunk_over_selected_latent_rows_compiles_and_never_takes_the_chunk_form(
        one_chip, monkeypatch):
    """The decode chunk of deepseek-v3.2-l5e16 (the module's catch-up, then
    8 verify steps over 16 slots of 16,384 at the widest decode window:
    five indexed latent layers and the module's block, two positions a
    row): with an indexer and two queries a slot every block scores,
    selects and gathers a set a position (``attn_latent_sparse_verify``)
    and no block walks a row's blocks as a prefill chunk does; the grouped
    expert products are in it; beside 11.38 GB of weights and 2.42 GB of
    state its temporaries (the gathered rows, 84 MB a block, and a
    block's float32 index scores, 134 MB) stay under half a GB."""
    import json
    from pathlib import Path

    from generativeaiexamples_tpu.engine.serving_models import HybridServing
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.ops import dispatch, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(dispatch, "TAKEN", {})
    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / "deepseek-v3.2-l5e16.json").read_text())
    engine = model["engine"]
    max_len, b, steps = int(engine["max_len"]), int(engine["max_batch"]), int(engine["decode_chunk_size"])
    cfg = hybrid.from_hf_config(model, max_len=max_len, kv_dtype=engine["kv_dtype"], draft=engine["draft"])
    assert cfg.draft == "mtp" and cfg.mtp_kind == ("mla", "experts") and len(cfg.layers_of("mla")) == 5
    serving = HybridServing(cfg, None, max_len)

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    spec = _spec(one_chip)
    ints, floats = spec((b,), jnp.int32), spec((b,), jnp.float32)
    compiled = serving.make_decode_chunk().lower(
        described(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))),
        described(lambda: hybrid.init_state(cfg, b, max_len)),
        ints, ints, spec((2,), jnp.uint32), floats, floats, ints, steps, max_len,
        spec((b,), jnp.bool_), spec((1, b), jnp.int32), spec((b,), jnp.bool_),
        ints, spec((b,), jnp.bool_),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    taken = dict(dispatch.TAKEN)
    for site in (f"index_scores b={b} s=2 t={max_len}", f"attn_latent_sparse_verify b={b} t={max_len} k=2048",
                 f"mtp_index_scores b={b} s=2 t={max_len}", f"mtp_attn_latent_sparse_verify b={b} t={max_len} k=2048",
                 f"mtp_attn_latent_sparse_decode b={b} t={max_len} k=2048"):
        assert taken[site] == "xla", (site, taken)
    assert not [site for site in taken if "chunk" in site], taken
    assert {taken[site] for site in taken if site.startswith("moe_experts")} == {"pallas"}
    _, toks, counts, (newest, lengths), aux = compiled.out_info
    assert toks.shape == (steps, b, 2) and counts.shape == (steps, b)
    assert newest.shape == (1, b) and lengths.shape == (b,)
    assert aux.shape == (len(serving.counter_names),)
    print("verify chunk temporaries", compiled.memory_analysis().temp_size_in_bytes)
    assert compiled.memory_analysis().temp_size_in_bytes < 500_000_000


def test_the_cca_models_decode_chunk_walks_its_rows_in_place(one_chip, monkeypatch):
    """The decode chunk of zaya1-8b-l20 (8 steps over 32 slots of 8,192
    rows, twenty ``cca`` layers): every layer's attention is the row walk
    of ``ops/gqa_decode.py`` at 8 query heads on 2 key-value heads over
    rows 256 wide, the scatter that writes a step's rows feeds it in place
    (no K or V leaf is copied), and beside 14.75 GB of weights and state
    the program's temporaries are the float32 logits of 32 rows x 262,272
    and little else."""
    import json
    from pathlib import Path

    from generativeaiexamples_tpu.engine.serving_models import HybridServing
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.ops import gqa_decode, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / "zaya1-8b-l20.json").read_text())
    engine = model["engine"]
    max_len, b, steps = int(engine["max_len"]), int(engine["max_batch"]), int(engine["decode_chunk_size"])
    cfg = hybrid.from_hf_config(model, max_len=max_len, kv_dtype=engine["kv_dtype"])
    assert isinstance(cfg, hybrid.CcaConfig) and cfg.n_layers == 20
    serving = HybridServing(cfg, None, max_len)

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    spec = _spec(one_chip)
    ints, floats = spec((b,), jnp.int32), spec((b,), jnp.float32)
    compiled = serving.make_decode_chunk().lower(
        described(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))),
        described(lambda: hybrid.init_state(cfg, b, max_len)),
        ints, ints, spec((2,), jnp.uint32), floats, floats, ints, steps, max_len,
        spec((b,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert text.count("gqa_rows_decode_attention") >= 20  # a walk a layer
    assert not re.search(rf"= bf16\[{b},{max_len},256\]\S* copy\(", text)
    _, toks, aux = compiled.out_info
    assert toks.shape == (steps, b) and aux.shape == (len(serving.counter_names),)
    memory = compiled.memory_analysis()
    print("cca decode chunk temporaries", memory.temp_size_in_bytes)
    assert memory.temp_size_in_bytes < 200_000_000
    assert memory.alias_size_in_bytes >= 5_368_709_120  # the slots' state goes through in place


def test_the_mamba_models_decode_chunk_updates_its_state_in_place(one_chip, monkeypatch):
    """The decode chunk of nemotron-3-super-120b-a12b-l11e128 (8 steps over
    32 slots: five ``mamba`` layers, five expert layers in a latent, one
    ``full`` layer): the slots' 0.95 GB of state goes through in place (no
    copy of a layer's ``S``, 537 MB of float32), the one attention layer is
    the row walk of ``ops/gqa_decode.py`` at 32 query heads on 2 key-value
    heads, the experts the grouped products at tiles that divide 1,024 and
    2,688, and the program's temporaries stay small beside 10.25 GB of
    weights and state."""
    import json
    from pathlib import Path

    from generativeaiexamples_tpu.engine.serving_models import HybridServing
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.ops import gqa_decode, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / "nemotron-3-super-120b-a12b-l11e128.json").read_text())
    engine = model["engine"]
    max_len, b, steps = int(engine["max_len"]), int(engine["max_batch"]), int(engine["decode_chunk_size"])
    cfg = hybrid.from_hf_config(model, max_len=max_len, kv_dtype=engine["kv_dtype"])
    assert isinstance(cfg, hybrid.MambaConfig) and cfg.n_layers == 6
    serving = HybridServing(cfg, None, max_len)

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    spec = _spec(one_chip)
    ints, floats = spec((b,), jnp.int32), spec((b,), jnp.float32)
    compiled = serving.make_decode_chunk().lower(
        described(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))),
        described(lambda: hybrid.init_state(cfg, b, max_len)),
        ints, ints, spec((2,), jnp.uint32), floats, floats, ints, steps, max_len,
        spec((b,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert "gqa_rows_decode_attention" in text and "gmm" in text
    assert not re.search(rf"= f32\[{b},128,64,128\]\S* copy\(", text)
    _, toks, aux = compiled.out_info
    assert toks.shape == (steps, b) and aux.shape == (len(serving.counter_names),)
    memory = compiled.memory_analysis()
    print("mamba decode chunk temporaries", memory.temp_size_in_bytes)
    assert memory.temp_size_in_bytes < 200_000_000
    assert memory.alias_size_in_bytes >= 949_354_496  # S, the tails and the K/V rows, in place


def test_the_latent_models_decode_chunk_keeps_its_state_in_place(one_chip, monkeypatch):
    """The decode chunk of mistral-small-4-119b-l6e32 (8 absorbed steps
    over 16 slots) at the widest decode window, 32,768: the slots' latent
    rows go in and come out in the layout they are stored in.  A row of
    320 columns is no whole number of lanes, the chip's default layout of
    such a leaf puts the POSITIONS minor, and the program then copied every
    layer's 0.34 GB in and out (2.5 GB of temporaries); rows of 384
    columns keep the layout the steps work in, and the whole-row
    contraction never cuts a row into its latent and its rope key.  Each
    layer's ``layer/mla/attn`` is ``ops/mla_decode.py``'s Mosaic call over
    all 16 rows: no ``dynamic-slice`` of a block of 2,048 rows and no
    ``conditional`` a slot is left of ``attend_absorbed_blocks``' loop, and
    the leaf goes into the call and past it where it lies in HBM (no
    ``copy`` or ``copy-start`` of it: memory-space assignment prefetches
    nothing of a 403 MB operand)."""
    import json
    from pathlib import Path

    from generativeaiexamples_tpu.engine.serving_models import HybridServing
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.ops import gqa_decode, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / "mistral-small-4-119b-l6e32.json").read_text())
    engine = model["engine"]
    max_len, b, steps = int(engine["max_len"]), int(engine["max_batch"]), int(engine["decode_chunk_size"])
    cfg = hybrid.from_hf_config(model, max_len=max_len, kv_dtype=engine["kv_dtype"])
    assert cfg.latent_width == 384 and cfg.latent_width % 128 == 0
    serving = HybridServing(cfg, None, max_len)

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    spec = _spec(one_chip)
    ints, floats = spec((b,), jnp.int32), spec((b,), jnp.float32)
    compiled = serving.make_decode_chunk().lower(
        described(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))),
        described(lambda: hybrid.init_state(cfg, b, max_len)),
        ints, ints, spec((2,), jnp.uint32), floats, floats, ints, steps, max_len,
        spec((b,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the grouped expert products
    assert not re.search(rf"= bf16\[{b},{max_len},384\]\S* copy\(", text)
    assert not re.search(rf"= bf16\[{b},{max_len},(?:256|64|320)\]", text)  # no row cut in two
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*latent_decode_attention", text)
    assert len(calls) >= cfg.n_layers  # a walk a layer, every row of the step in it
    assert all("layer/mla/attn" in call for call in calls)
    leaf = rf"bf16\[{b},{max_len},384\]"
    assert not re.search(rf"= (?:\([^=]*)?{leaf}\S*(?:, [^=]*\))? copy-start\(", text)
    assert not re.search(r"bf16\[1,2048,384\]\S* dynamic-slice\(", text)
    assert not re.search(r"\(bf16\[1,1,32,128\]\S*\) conditional\(", text)  # no branch a slot
    _, toks, aux = compiled.out_info
    assert toks.shape == (steps, b) and aux.shape == (len(serving.counter_names),)
    print("latent decode chunk temporaries", compiled.memory_analysis().temp_size_in_bytes)
    assert compiled.memory_analysis().temp_size_in_bytes < 400_000_000


def test_the_shortcut_models_decode_chunk_walks_eight_sublayers_in_place(one_chip, monkeypatch):
    """The decode chunk of longcat-flash-chat-l4e16 (4 steps over 16 slots
    of 16,384, four published layers: eight latent sublayers, eight dense
    MLPs, four expert layers whose sum crosses a sublayer) at the widest
    decode window: every sublayer's ``layer/mla/attn`` is
    ``ops/mla_decode.py``'s Mosaic call over all 16 rows (64 query rows a
    slot over a latent of 512: the width PR 56's kernel had not met without
    an indexer in front), the grouped expert products are in it, no leaf of
    the slots' state is copied, and beside 10.35 GB of weights and 2.68 GB
    of state its temporaries stay near half a GB (0.46 here)."""
    import json
    from pathlib import Path

    from generativeaiexamples_tpu.engine.serving_models import HybridServing
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.ops import dispatch, gqa_decode, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(dispatch, "TAKEN", {})
    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / "longcat-flash-chat-l4e16.json").read_text())
    engine = model["engine"]
    max_len, b, steps = int(engine["max_len"]), int(engine["max_batch"]), int(engine["decode_chunk_size"])
    cfg = hybrid.from_hf_config(model, max_len=max_len, kv_dtype=engine["kv_dtype"])
    assert cfg.latent_width == 640 and len(cfg.layers_of("mla")) == 8 and cfg.router_outputs == 768
    serving = HybridServing(cfg, None, max_len)

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    spec = _spec(one_chip)
    ints, floats = spec((b,), jnp.int32), spec((b,), jnp.float32)
    compiled = serving.make_decode_chunk().lower(
        described(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))),
        described(lambda: hybrid.init_state(cfg, b, max_len)),
        ints, ints, spec((2,), jnp.uint32), floats, floats, ints, steps, max_len,
        spec((b,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    taken = dict(dispatch.TAKEN)
    assert taken[f"attn_latent_decode b={b} t={max_len}"] == "pallas", taken
    assert {taken[site] for site in taken if site.startswith("moe_experts")} == {"pallas"}
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*latent_decode_attention", text)
    assert len(calls) >= cfg.n_layers and all("layer/mla/attn" in call for call in calls)
    leaf = rf"bf16\[{b},{max_len},640\]"
    assert not re.search(rf"= {leaf}\S* copy\(", text)
    assert not re.search(rf"= (?:\([^=]*)?{leaf}\S*(?:, [^=]*\))? copy-start\(", text)
    _, toks, aux = compiled.out_info
    assert toks.shape == (steps, b) and aux.shape == (len(serving.counter_names),)
    assert "moe_choices_zero" in serving.counter_names
    print("shortcut decode chunk temporaries", compiled.memory_analysis().temp_size_in_bytes)
    assert compiled.memory_analysis().temp_size_in_bytes < 600_000_000


def test_lings_decode_chunk_touches_the_kda_state_by_the_kernel_alone(one_chip, monkeypatch):
    """The decode chunk of ling-3.0-flash-vl-l7e128 (8 steps over 32
    slots) at the widest decode window: each of the six KDA layers' state
    leaves ``f32[32,32,128,128]`` is read and written by the step kernel
    in place; XLA's twin made two fusions over it and three passes, every
    slot's (PERF.md, PR 39)."""
    import json
    from pathlib import Path

    from generativeaiexamples_tpu.engine.serving_models import HybridServing
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.ops import kda, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(kda, "platform_of", lambda mesh: "tpu")
    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / "ling-3.0-flash-vl-l7e128.json").read_text())
    engine = model["engine"]
    max_len, b, steps = int(engine["max_len"]), int(engine["max_batch"]), int(engine["decode_chunk_size"])
    cfg = hybrid.from_hf_config(model, max_len=max_len, kv_dtype=engine["kv_dtype"])
    layers = len(cfg.layers_of("kda"))
    assert (b, cfg.n_heads, cfg.kda_head_dim, cfg.kda_head_dim) == KDA_STATE and layers == 6
    serving = HybridServing(cfg, None, max_len)

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    spec = _spec(one_chip)
    ints, floats = spec((b,), jnp.int32), spec((b,), jnp.float32)
    compiled = serving.make_decode_chunk().lower(
        described(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))),
        described(lambda: hybrid.init_state(cfg, b, max_len)),
        ints, ints, spec((2,), jnp.uint32), floats, floats, ints, steps, max_len,
        spec((b,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    _state_is_the_kernels_alone(text, calls=layers)
    _, toks, aux = compiled.out_info
    assert toks.shape == (steps, b) and aux.shape == (len(serving.counter_names),)
    print("ling decode chunk temporaries", compiled.memory_analysis().temp_size_in_bytes)
    assert compiled.memory_analysis().temp_size_in_bytes < 600_000_000
