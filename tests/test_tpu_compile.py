"""The main path's kernels, compiled for the chip without the chip.

The TPU compiler is installed with JAX and compiles for a topology that
is described, not attached (``v5e:2x2``, device kind "TPU v5 lite"), so
what it refuses — a slice off the tiling, too much VMEM, a program that
does not fit — is found here and not on chip time.  Nothing runs: a
compile that passes is not a chip run.  Interpret-mode tests cannot see
any of this.

Shapes are GPT-small's on one chip at batch 4 x sequence 2048
(``chip_smoke.py``), the train cells' own attention call (GPT-2 medium,
4 x 16 heads of 1024 x 64), one attention shape past the VMEM budget
(``tile_plan`` streams it), and ZeRO's 4 MiB bucket on the four devices.
"""

import base64
import hashlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from kungfu_tpu.ops.pallas.attention import flash_attention, tile_plan
from kungfu_tpu.ops.pallas.collectives import (ring_all_gather,
                                               ring_reduce_scatter)
from kungfu_tpu.ops.pallas.lm_head import lm_head_nll
from kungfu_tpu.ops.pallas.xent import softmax_cross_entropy


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this jaxlib
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A described-device compile can be written to the persistent cache
    but not read back; keep it off so these stay silent."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


N, D, V = 8192, 768, 32128          # tokens on a chip, d_model, vocab
QKV = (4, 12, 2048, 64)             # [B, H, S, head_dim]
QKV_CELL = (4, 16, 1024, 64)        # gpt2m-train-*: what the cells call
QKV_LONG = (1, 8, 8192, 128)        # past the VMEM budget: streamed
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
BUCKET = (4 << 20) // 4             # ZeRO's 4 MiB bucket, in f32 elements


def _flash(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False)


def _xent(logits, targets):
    return softmax_cross_entropy(logits, targets, interpret=False)


def _head(h, w, targets):
    return lm_head_nll(h, w, targets, interpret=False)


def _grad(f, argnums):
    return jax.grad(lambda *a: f(*a).astype(jnp.float32).mean(), argnums)


def _ring(fn, bidirectional):
    return lambda x: fn(x, "d", bidirectional=bidirectional, impl="pallas",
                        interpret=False)


bf16, i32 = jnp.bfloat16, jnp.int32
QKV3 = [(QKV, bf16)] * 3
QKV3_CELL = [(QKV_CELL, bf16)] * 3
QKV3_LONG = [(QKV_LONG, bf16)] * 3
XENT = [((N, V), bf16), ((N,), i32)]
HEAD = [((N, D), bf16), ((D, V), bf16), ((N,), i32)]

#: (id, function, [(shape, dtype), ...], chips, kernels expected)
CASES = [
    ("flash_fwd", _flash, QKV3, 1, 1),
    ("flash_fwd_bwd", _grad(_flash, (0, 1, 2)), QKV3, 1, 3),
    ("flash_fwd_cell", _flash, QKV3_CELL, 1, 1),
    ("flash_fwd_bwd_cell", _grad(_flash, (0, 1, 2)), QKV3_CELL, 1, 3),
    ("flash_fwd_streamed", _flash, QKV3_LONG, 1, 1),
    ("flash_fwd_bwd_streamed", _grad(_flash, (0, 1, 2)), QKV3_LONG, 1, 3),
    ("xent_fwd", _xent, XENT, 1, 1),
    ("xent_fwd_bwd", _grad(_xent, 0), XENT, 1, 2),
    ("lm_head_fwd", _head, HEAD, 1, 1),
    ("lm_head_fwd_bwd", _grad(_head, (0, 1)), HEAD, 1, 3),
    ("ring_reduce_scatter", _ring(ring_reduce_scatter, False),
     [((4 * BUCKET,), jnp.float32)], 4, 1),
    ("ring_reduce_scatter_bidir", _ring(ring_reduce_scatter, True),
     [((4 * BUCKET,), jnp.float32)], 4, 1),
    ("ring_all_gather", _ring(ring_all_gather, False),
     [((BUCKET,), jnp.float32)], 4, 1),
    ("ring_all_gather_bidir", _ring(ring_all_gather, True),
     [((BUCKET,), jnp.float32)], 4, 1),
]


def _lower(topo, fn, args, chips):
    if chips == 1:
        sharding = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices[:chips]), ("d",))
        sharding = NamedSharding(mesh, P("d"))
        fn = shard_map(fn, mesh=mesh, in_specs=P("d"), out_specs=P("d"))
    return jax.jit(fn).lower(*[
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in args])


@pytest.mark.parametrize("name,fn,args,chips,kernels", CASES,
                         ids=[c[0] for c in CASES])
def test_compiles_for_v5e(topo, name, fn, args, chips, kernels):
    text = _lower(topo, fn, args, chips).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == kernels
    if name.startswith("flash"):
        # the benchmark's flash_ms_per_step / flash_roofline and
        # compile_for_chip.py find the kernels by these names
        called = re.findall(r"^\s*%?([\w.\-]+) = [^\n]*"
                            r'custom_call_target="tpu_custom_call"', text, re.M)
        for kernel in FLASH_KERNELS[:kernels]:
            assert len([c for c in called if re.search(
                kernel + r"(?![a-z])", c)]) == 1, called


def test_flash_shapes_take_both_paths():
    """The cases above cover both branches of ``tile_plan``."""
    assert tile_plan(*QKV[-2:], bf16, True).path == "resident"
    assert tile_plan(*QKV_CELL[-2:], bf16, True).path == "resident"
    assert tile_plan(*QKV_LONG[-2:], bf16, True).path == "streamed"


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@pytest.mark.parametrize("qkv", [QKV_CELL, QKV_LONG],
                         ids=["resident", "streamed"])
def test_flash_products_take_bf16_operands(qkv):
    """With bfloat16 inputs no product of the three kernels has two
    float32 operands (the MXU does those in several bfloat16 passes):
    q, k, v and dO meet it as they arrive, P and dS cast to their
    partner's type, and every product accumulates in float32."""
    x = jax.ShapeDtypeStruct(qkv, bf16)
    jaxpr = jax.make_jaxpr(_grad(_flash, (0, 1, 2)))(x, x, x).jaxpr
    kernels = {e.params["name"]: e.params["jaxpr"] for e in _equations(jaxpr)
               if e.primitive.name == "pallas_call"}
    assert sorted(kernels) == sorted(FLASH_KERNELS)
    products = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
    for name, kernel in kernels.items():
        dots = [e for e in _equations(kernel)
                if e.primitive.name == "dot_general"]
        # the walk over the tiles is unrolled: every run of tiles writes
        # the kernel's products once
        assert dots and len(dots) % products[name] == 0, (name, len(dots))
        for e in dots:
            assert [v.aval.dtype for v in e.invars] == [bf16, bf16], (name, e)
            assert e.outvars[0].aval.dtype == jnp.float32, (name, e)


@pytest.mark.parametrize("fn,per_device_mib,needs", [
    (ring_reduce_scatter, 128, "192.0 MiB"),  # GPT-small's embedding scale
    (ring_all_gather, 12, "24.0 MiB"),
], ids=["reduce_scatter", "all_gather"])
def test_over_vmem_chunk_is_refused_at_trace_time(topo, fn, per_device_mib,
                                                  needs):
    """The ring kernels hold whole chunks in VMEM; past the budget the
    compiler's answer is an allocation dump from inside the step.  The
    kernel entry says it first, with the numbers."""
    elems = (per_device_mib << 20) // 4
    with pytest.raises(ValueError) as e:
        _lower(topo, _ring(fn, False), [((4 * elems,), jnp.float32)], 4)
    msg = str(e.value)
    assert "VMEM" in msg and "16 MiB" in msg and needs in msg


def test_vmem_budget_is_the_compilers(topo):
    """Just under the budget compiles: the trace-time check does not
    refuse what the compiler accepts (10 MiB a device: 15 MiB scratch)."""
    elems = (10 << 20) // 4
    _lower(topo, _ring(ring_reduce_scatter, False),
           [((4 * elems,), jnp.float32)], 4).compile()


# -- the serving engine's programs move no slab --------------------------------
#: GPT-2 large with 16 slots of 1024 positions: the chat cell's programs
#: (at fewer layers the compiler, with memory to spare, makes other choices)
SERVE_LAYERS, SERVE_SLOTS, SERVE_SEQ = 36, 16, 1024


@pytest.fixture(scope="module")
def serve_programs(topo):
    """name -> compiled text of the engine's three slab writers, lowered
    from shapes alone (no array of that size is made here)."""
    from kungfu_tpu.models.transformer import Transformer, TransformerConfig
    from kungfu_tpu.serve.engine import InferenceEngine
    from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec

    cfg = TransformerConfig(vocab_size=50257, d_model=1280,
                            n_layers=SERVE_LAYERS, n_heads=20, d_ff=5120,
                            max_seq=SERVE_SEQ, dropout=0.0, causal=True,
                            pos="learned", dtype="bfloat16")
    model = Transformer(cfg)
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree_util.tree_map(
        lambda x: shaped(x.shape, x.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    # a one-page engine: its programs take the slab's size from the slab
    eng = InferenceEngine(
        model, None, max_batch=SERVE_SLOTS, max_seq=16,
        pool=KVCachePool(PageSpec.for_model(cfg, page_tokens=16),
                         capacity_pages=1))
    slab = shaped((cfg.n_layers, SERVE_SLOTS, cfg.n_heads, SERVE_SEQ,
                   cfg.head_dim), bf16)
    pages = shaped((cfg.n_layers, cfg.n_heads, 256, cfg.head_dim), bf16)
    slots, i0 = shaped((SERVE_SLOTS,), i32), shaped((), i32)
    lowered = {
        "decode": eng._decode_j.lower(params, slab, slab, slots, slots,
                                     slots),
        "prefill": eng._prefill_j.lower(params, slab, slab,
                                        shaped((256,), i32), i0, i0, i0),
        "restore": eng._restore_j.lower(slab, slab, pages, pages, i0),
    }
    return {name: lo.compile().as_text() for name, lo in lowered.items()}


@pytest.mark.parametrize("program", ["decode", "prefill", "restore"])
def test_serving_program_writes_its_slab_in_place(serve_programs, program):
    """Both slabs alias their outputs, and no operation of the compiled
    program but the in-place updates produces a slab or a layer of one:
    no copy of either, no materialised layer slice (PERF.md, PR 25: those were 54 of a 66 ms decode step)."""
    text = serve_programs[program]
    assert len(re.findall(r"may-alias|must-alias",
                          text.split("\n", 1)[0])) == 2
    layer = f"{SERVE_SLOTS},20,{SERVE_SEQ},64"
    entry = text[text.index("\nENTRY"):]
    moved = []
    for name, dims, op in re.findall(
            r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(",
            entry, re.M):
        in_place = (op == "dynamic-update-slice"
                    or "dynamic-update-slice" in name
                    or "dynamic_update_slice" in name)
        if dims.endswith(layer) and not in_place and op not in (
                "parameter", "bitcast", "get-tuple-element", "tuple"):
            moved.append((op, name, dims))
    assert not moved


def test_decode_row_write_is_one_fused_window_update(serve_programs):
    """The decode step writes a slot's row as one in-place fusion over the
    aligned window that holds it (K and V together), not as a plain
    ``dynamic-update-slice``: that one is unrolled, and took 9.4 ms a
    step where the fusions take 1.9 (PERF.md, PR 25)."""
    entry = serve_programs["decode"]
    entry = entry[entry.index("\nENTRY"):]
    assert not re.findall(r" dynamic-update-slice\(", entry)
    fused = re.findall(r"^\s*%?[\w.\-]*dynamic-update-slice_fusion[\w.]* = ",
                       entry, re.M)
    assert len(fused) == SERVE_LAYERS * SERVE_SLOTS


# -- the serving families' programs are what they were ---------------------------
#: family -> program -> sha256 of the text its fixture below lowers for
#: the described chip, as PR 46's tree lowered it (the kernels' forms;
#: tests/test_cohere2_moe.py holds XLA's, at the tiny sizes), each
#: kernel in it without its source locations (:func:`_located_nowhere`).
#: A change that moves the cache managers' code between functions leaves
#: every one as it is: it makes the same operations in the same order.
#: A PR that changes a family's programs on purpose records its own.
AS_BEFORE = {
    "moe": {
        "decode":
            "5482f0d6bcd37cc473ae111ee2efc9f3fe9098bdebbc5696ad8ed10f1fe0e88d",
        "restore":
            "2c2b58ffd437ade8d80bf44d1c58ee846b565ceb226095b797106197447b3433",
        "prefill256":
            "890347cc18586c8bfa86df15745d2fab30eee06fd0a78aa884016470625c35a1",
        "prefill8192":
            "0568e02c678eca3c7f5626a7aa18d41a3c3caac1e39fa949d923a18adb87f0a6",
    },
    "mla": {
        "decode":
            "95694174fb111e6bd0b2e1199af6003315051818b2aed7b458630b89d9d09230",
        "restore":
            "6effc29371aeccfcb3411476c7257260aa3761e1ade07cd9dd2781f4e3bd8f64",
        "prefill256":
            "861463a6135baca324141f1af0647032c1d55a9f914c427753767fa4c5c55978",
        "prefill16384":
            "7542230a0cfe8cb0d1a318c521f71f2da223cca6147afd5f32b82b5deec40a70",
    },
    "kda": {
        "decode":
            "fcc83f47411a7ec3e8ab89d1918c7670cf357e06117feaee3bf6e2bc3a5824e3",
        "restore":
            "65ba70c415841a86d0aead0a496289c396a151eff35873f59f6cf2f63697670b",
        "prefill256":
            "457a4cc12084bbf9204ce60409340ede6ada099e50fab322af2d890cfb4ceade",
        "prefill2048":
            "29fdb8aa937f720a615c3589fafa46445348b6b2feb3a5d26b0396e3f00471a3",
    },
    "eva": {
        "decode":
            "97d5e5bf47383b6c472d8a2cfbff0d29eedecaf3cd26e612d47b5b28d165e809",
        "prefill32768":
            "cdf5c747b9cbd71a82792f79b7520fb0a1662939ff534f4e458cc211c642e924",
    },
    "sambay": {
        "decode":
            "fc35d7038ac6e1c76848c20d69753e78b289b29c258f0e1a21cb465f2f5b8d8e",
        "prefill1024":
            "b2d616e86c716546c66cefff9166489cdece949f73d775bd54562554f0f261d6",
    },
}
_LOWERED = {}


def _located_nowhere(text):
    """``text`` with the body of every Mosaic kernel in it -- MLIR
    bytecode in base64, serialized WITH its debug information: the
    file, line and function of the ten Python frames above every
    operation of the kernel, the checkout's path among them -- replaced
    by that kernel's assembly without locations.  (The persistent
    compile cache's key keeps them: a program that calls a kernel is
    compiled anew after any edit that moves a line of a frame above the
    call, PERF.md PR 47.)"""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    ctx = mlir.JaxIrContext()
    ctx.allow_unregistered_dialects = True      # ``stable_mosaic``

    def bare(found):
        with ctx:
            return ir.Module.parse(base64.b64decode(found[1])
                                   ).operation.get_asm(enable_debug_info=False)

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', bare, text)


def _compiled(family, lowered):
    """name -> compiled program of ``lowered``, each one's text kept as
    its sha256 under ``_LOWERED[family]`` for the test that holds it to
    ``AS_BEFORE``."""
    _LOWERED[family] = {
        name: hashlib.sha256(_located_nowhere(lo.as_text()).encode()
                             ).hexdigest()
        for name, lo in lowered.items()}
    return {name: lo.compile() for name, lo in lowered.items()}


# -- the same guarantee for the model with two caches -----------------------------
#: command-a-plus-05-2026 as its cell serves it: one period of layers,
#: 16 of 128 experts, 32 slots of 8192 positions, rings of 4096
MOE_SLOTS, MOE_SEQ, MOE_RING, MOE_HELD = 32, 8192, 4096, 16
MOE_PREFILL = (256, 8192)


@pytest.fixture(scope="module")
def moe_programs(topo):
    """name -> compiled program of the engine serving ``cohere2_moe`` at
    the published widths, lowered from shapes alone."""
    from kungfu_tpu.models.cohere2_moe import Cohere2Moe, Cohere2MoeConfig
    from kungfu_tpu.serve.engine import InferenceEngine
    from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec

    cfg = Cohere2MoeConfig(vocab_size=32768, n_layers=4,
                           experts_held=(0, MOE_HELD), max_seq=MOE_SEQ)
    model = Cohere2Moe(cfg)
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree_util.tree_map(
        lambda x: shaped(x.shape, x.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    eng = InferenceEngine(
        model, None, max_batch=MOE_SLOTS, max_seq=MOE_SEQ,
        pool=KVCachePool(PageSpec.for_model(cfg, page_tokens=256),
                         capacity_pages=1))
    slab = tuple(shaped(s, bf16) for s in eng._caches.shapes())
    assert [s.shape for s in slab] == [
        (3, MOE_SLOTS, 8, MOE_RING, 128), (1, MOE_SLOTS, 8, MOE_SEQ, 128)]
    pages = tuple(shaped(p.shape, bf16)
                  for p in eng._caches.empty_pages(256)[0])
    slots, i0 = shaped((MOE_SLOTS,), i32), shaped((), i32)
    lowered = {"decode": eng._decode_j.lower(
        params, slab, slab, shaped((MOE_SLOTS + 3,), i32), slots, slots),
               "restore": eng._restore_j.lower(slab, slab, pages, pages, i0)}
    for n in MOE_PREFILL:
        lowered[f"prefill{n}"] = eng._prefill_j.lower(
            params, slab, slab, shaped((n,), i32), i0, i0, i0)
    return _compiled("moe", lowered)


def _entry_ops(text):
    """(name, dtype, dims, operation) of the entry computation's
    instructions."""
    entry = text[text.index("\nENTRY"):]
    return re.findall(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(",
        entry, re.M)


@pytest.mark.parametrize("program", ["decode", "restore"]
                         + [f"prefill{n}" for n in MOE_PREFILL])
def test_two_cache_program_writes_its_slabs_in_place(moe_programs, program):
    """All four slabs (a ring and a full slab, for K and for V) alias
    their outputs, and nothing but the in-place updates produces an
    array the size of a slab, of one layer of one, or of the weights of a
    layer's experts or query projection: no copy of a slab, no
    materialised layer, no weights laid out anew every step."""
    assert _LOWERED["moe"][program] == AS_BEFORE["moe"][program]
    text = moe_programs[program].as_text()
    assert len(re.findall(r"may-alias|must-alias",
                          text.split("\n", 1)[0])) == 4
    moved = []
    for name, dtype, dims, op in _entry_ops(text):
        elems = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
        in_place = "dynamic-update-slice" in name or "dynamic_update_slice" \
            in name or op == "dynamic-update-slice"
        # the smallest of those: a layer of the ring, 32 x 8 x 4096 x 128
        if elems >= MOE_SLOTS * 8 * MOE_RING * 128 and not in_place \
                and op not in ("parameter", "bitcast", "get-tuple-element",
                               "tuple"):
            moved.append((op, name, dtype, dims))
    assert not moved


def test_decode_expert_product_has_the_same_shapes_whatever_the_routing(
        moe_programs):
    """The routed product of a decode step is one batched product over
    every held expert and every slot: in the compiled program each
    layer's three products carry ``[16, 32, 4096]`` whatever the tokens
    chose, there is no grouped (``ragged``) product whose work follows
    the routing, and no loop or branch under ``moe_experts``.  A step
    then reads every held expert every time, as a deployment's does
    (PERF.md, PR 27)."""
    text = moe_programs["decode"].as_text()
    assert "ragged" not in text
    entry = text[text.index("\nENTRY"):]
    weights = re.findall(
        r"%(params__layer_\d____moe____experts____(?:gate|up|down)__[.\d]*) = "
        r"bf16\[16,4096,4096\]", entry)
    assert len(weights) == 4 * 3
    for w in weights:
        # one reader each: a product over all 16 experts at once, of
        # static shape (the compiler joins gate and up, and the down
        # product with the weighted sum, into one fusion each)
        readers = re.findall(
            r"^\s*%?[\w.\-]+ = (\w+)\[([\d,]+)\]\S* ([\w\-]+)\([^\n]*%"
            + re.escape(w) + r"[,)][^\n]*op_name=\"([^\"]*)\"", entry, re.M)
        assert len(readers) == 1, (w, readers)
        dtype, dims, op, name = readers[0]
        assert op in ("fusion", "convolution"), readers
        assert "/mlp/moe_experts/" in name and "dot_general" in name
        assert dims in (f"{MOE_HELD},{MOE_SLOTS},4096", f"{MOE_SLOTS},4096")
    assert not re.findall(
        r"= [^\n]* (while|conditional)\([^\n]*moe_experts", text)


def test_two_cache_programs_fit_beside_the_weights(moe_programs):
    """9.47 GB of weights and 2.68 GB of slabs are arguments; what a
    program adds is small for a decode step and under 2 GB for the
    longest prefill (no ``[heads, P, S]`` scores, no ``[P, heads * D]``
    queries: PERF.md, PR 27)."""
    stats = {n: p.memory_analysis() for n, p in moe_programs.items()}
    args = stats["decode"].argument_size_in_bytes
    assert 12.1e9 < args < 12.2e9
    assert stats["decode"].temp_size_in_bytes < 0.1e9
    assert stats["prefill256"].temp_size_in_bytes < 0.5e9
    assert stats["prefill8192"].temp_size_in_bytes < 2.0e9


# -- and for the model with a latent cache ---------------------------------------
#: openPangu-Ultra-MoE-718B as its cell serves it: a dense layer and four
#: expert layers, 8 of 256 experts, an eighth of the vocabulary, 32 slots
#: of 16,384 positions
MLA_SLOTS, MLA_SEQ, MLA_LAYERS, MLA_HELD = 32, 16384, 5, 8
MLA_PREFILL = (256, 16384)
MLA_SAYS = 5        # what a step's ``out`` holds behind the tokens


@pytest.fixture(scope="module")
def mla_programs(topo):
    """name -> compiled program of the engine serving ``pangu_moe`` at
    the published widths, lowered from shapes alone."""
    from kungfu_tpu.models.pangu_moe import PanguMoe, PanguMoeConfig
    from kungfu_tpu.serve.engine import InferenceEngine
    from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec

    cfg = PanguMoeConfig(vocab_size=19200, n_layers=MLA_LAYERS,
                         init_layers=61, n_dense=1,
                         experts_held=(0, MLA_HELD), max_seq=MLA_SEQ)
    model = PanguMoe(cfg)
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree_util.tree_map(
        lambda x: shaped(x.shape, x.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    spec = PageSpec.for_model(cfg, page_tokens=256)
    # 1,152 bytes a position a layer, against 81,920 of per-head K and V
    assert spec.page_bytes == MLA_LAYERS * 256 * (512 + 64) * 2
    eng = InferenceEngine(model, None, max_batch=MLA_SLOTS, max_seq=MLA_SEQ,
                          pool=KVCachePool(spec, capacity_pages=1))
    c, k_r = (shaped(s, bf16) for s in eng._caches.shapes())
    assert (c.shape, k_r.shape) == (
        (MLA_LAYERS, MLA_SLOTS, 1, MLA_SEQ, 512),
        (MLA_LAYERS, MLA_SLOTS, 1, MLA_SEQ, 64))
    ks, vs = (shaped(p.shape, bf16) for p in eng._caches.empty_pages(256))
    slots, i0 = shaped((MLA_SLOTS,), i32), shaped((), i32)
    # the program asks the platform which form its decode attention takes
    # (``pangu_moe.absorbed_tile``); here it is told what the chip says
    with pytest.MonkeyPatch.context() as steer:
        steer.setattr(jax, "default_backend", lambda: "tpu")
        lowered = {"decode": eng._decode_j.lower(
            params, c, k_r, shaped((MLA_SLOTS + MLA_SAYS,), i32), slots,
            slots), "restore": eng._restore_j.lower(c, k_r, ks, vs, i0)}
        for n in MLA_PREFILL:
            lowered[f"prefill{n}"] = eng._prefill_j.lower(
                params, c, k_r, shaped((n,), i32), i0, i0, i0)
        assert eng._caches.latent_attn_kernel == 1
    return _compiled("mla", lowered)


@pytest.mark.parametrize("program", ["decode", "restore"]
                         + [f"prefill{n}" for n in MLA_PREFILL])
def test_latent_program_writes_its_slab_in_place(mla_programs, program):
    """Both parts of the latent slab alias their outputs, and nothing but
    the in-place updates produces an array the size of a part or of one
    layer of one: no copy of the slab, no materialised layer, and in the
    decode step no per-head key or value of a cached row -- ``[B, 128, S,
    128]`` would be 17 GB a layer (the prefill forms them for ONE slot)."""
    assert _LOWERED["mla"][program] == AS_BEFORE["mla"][program]
    text = mla_programs[program].as_text()
    assert len(re.findall(r"may-alias|must-alias",
                          text.split("\n", 1)[0])) == 2
    # a part, or one layer of one, with or without its axis of one head
    slab_like = re.compile(rf"(^|,){MLA_SLOTS},(1,)?{MLA_SEQ},(512|64)$")
    moved = []
    for name, dtype, dims, op in _entry_ops(text):
        in_place = "dynamic-update-slice" in name or "dynamic_update_slice" \
            in name or op == "dynamic-update-slice" \
            or _fused_root(text, name) == "dynamic-update-slice"
        if slab_like.search(dims) and not in_place and op not in (
                "parameter", "bitcast", "get-tuple-element", "tuple"):
            moved.append((op, name, dtype, dims))
        if program == "decode":     # the rows of a slot, expanded a head
            assert not re.search(rf"128,({MLA_SEQ},128|128,{MLA_SEQ})$",
                                 dims), (name, dims)
    assert not moved


def _fused_root(text, name):
    """The operation at the root of the computation a fusion calls (the
    16,384-row prefill joins the ``k_r`` projection with its write into
    the slab: one fusion, named after the product, whose root is the
    in-place update)."""
    line = re.search(r"^\s*(?:ROOT )?%?" + re.escape(name)
                     + r" = [^\n]*calls=%?([\w.\-]+)", text, re.M)
    if not line:
        return None
    body = text[text.index("\n%" + line.group(1) + " "):]
    root = re.search(r"^\s*ROOT [^\n]*?\]\S* ([\w\-]+)\(",
                     body[:body.index("\n}")], re.M)
    return root.group(1) if root else None


def _op_name(text, name):
    found = re.search(r"^\s*(?:ROOT )?%?" + re.escape(name)
                      + r" = [^\n]*op_name=\"([^\"]*)\"", text, re.M)
    return found.group(1) if found else ""


def test_latent_decode_row_write_is_a_fused_window_update(mla_programs):
    """The decode step writes a slot's latent row as in-place fusions over
    the aligned window that holds it, one for each part a slot and layer
    (``c_kv`` lies with its 512 values along the lanes, ``k_r`` with the
    positions along them: two layouts, so two loops), never as a plain
    ``dynamic-update-slice``."""
    entry = mla_programs["decode"].as_text()
    entry = entry[entry.index("\nENTRY"):]
    assert not re.findall(r" dynamic-update-slice\(", entry)
    fused = re.findall(r"^\s*%?[\w.\-]*dynamic-update-slice_fusion[\w.]* = ",
                       entry, re.M)
    assert len(fused) == 2 * MLA_LAYERS * MLA_SLOTS


def test_latent_decode_has_the_same_shapes_whatever_is_live(mla_programs):
    """No operation's SHAPE in the decode step follows the data: the
    shapes, not the time, are what stays.  The attention is ONE
    ``latent_attn`` kernel a layer handed the whole slab of every slot
    (its grid is fixed by the shapes: the absorbed order; which of its
    steps walk a tile follows the prefetched visible counts, and so does
    its time: PERF.md, PR 45), the routed product one batched product
    over every held expert, and nothing loops or branches.  The scores
    ``[32, 128, 16384]`` exist in no type, and the kernel is handed both
    parts of the slab as the row write left them: ``c`` itself, ``k_r``
    through a bitcast (its positions already lie along the lanes) -- no
    copy, slice or transpose of a part or of a layer of one."""
    text = mla_programs["decode"].as_text()
    assert "ragged" not in text
    entry = text[text.index("\nENTRY"):]
    assert not re.findall(r"= [^\n]* (while|conditional)\(", entry)
    assert not re.search(rf"\[{MLA_SLOTS},128,{MLA_SEQ}\]", text)
    calls = re.findall(
        rf"^\s*%?(latent_attn[\w.]*) = bf16\[{MLA_SLOTS},128,512\]\S* "
        r"custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"",
        entry, re.M)
    assert len(calls) == MLA_LAYERS
    entry_ops = _entry_ops(text)
    made_by = {name: op for name, _, _, op in entry_ops}
    for name, operands in calls:
        assert "/attn_core/mla_latent_attn/" in _op_name(text, name)
        c, k_r = (o.split("%")[-1].strip() for o in operands.split(",")[-2:])
        # ``c``: the fused in-place row write's result; ``k_r``: a bitcast
        assert made_by[c] == "fusion" and _fused_root(text, c) \
            == "dynamic-update-slice", (c, made_by[c])
        assert made_by[k_r] == "bitcast", (k_r, made_by[k_r])
    # a part, or a layer of one, with ``k_r`` as stored or as the kernel
    # takes it: nothing but the row writes, bitcasts and the parameters
    # themselves has such a shape
    part = re.compile(
        rf"(^|,){MLA_SLOTS},(1,)?({MLA_SEQ},(512|64)|64,{MLA_SEQ})$")
    for name, _, dims, op in entry_ops:
        if part.search(dims) and op not in (
                "parameter", "bitcast", "get-tuple-element", "tuple"):
            assert _fused_root(text, name) == "dynamic-update-slice", \
                (op, name, dims)
    # whatever else computes under the scope is sized by the slots and
    # the heads, or is one of the walk's scalars a slot
    paths = {name: _op_name(text, name) for name, _, _, op in entry_ops
             if op in ("fusion", "convolution", "custom-call", "copy",
                       "transpose")}
    for name, _, dims, _ in entry_ops:
        if "/attn_core/mla_latent_attn/" in paths.get(name, ""):
            assert dims in (f"{MLA_SLOTS},128,512", f"{MLA_SLOTS},128,64",
                            f"{MLA_SLOTS}", f"{MLA_SLOTS},1"), (name, dims)
    ops = [paths[name] for name, _, _, op in entry_ops
           if op in ("fusion", "convolution")]
    experts_w = re.findall(
        r"%(params__layer_\d____moe____experts____(?:gate|up|down)__[.\d]*) = "
        r"bf16\[8,(?:7680,2048|2048,7680)\]", entry)
    assert len(experts_w) == (MLA_LAYERS - 1) * 3
    # the absorption stays XLA's: W_uk goes into the query, W_uv into the
    # output, one product each a layer
    for product in ("bhn,hnc->bhc", "bhc,hcv->bhv"):
        assert sum(f"/{product}/dot_general" in path
                   for path in ops) == MLA_LAYERS, product


def test_latent_programs_fit_beside_the_weights(mla_programs):
    """6.83 GB of weights and the 3.02 GB slab at its TRUE size are
    arguments (32 x 16,384 x 5 x 576 x 2 bytes: the compiler pads neither
    part -- it lays ``k_r``'s positions along the lanes); a decode step
    adds some 15 MB (the float32 scores of one layer, 268 MB before the
    attention was one kernel, never reach memory), the longest prefill
    the expanded keys and values of one slot (1.07 GB), the stream twice
    and one tile's scores."""
    stats = {n: p.memory_analysis() for n, p in mla_programs.items()}
    args = stats["decode"].argument_size_in_bytes
    slab = MLA_LAYERS * MLA_SLOTS * MLA_SEQ * (512 + 64) * 2
    assert slab == 3_019_898_880
    assert stats["restore"].alias_size_in_bytes == slab
    assert 9.85e9 < args < 9.86e9 and 6.83e9 < args - slab < 6.84e9
    assert stats["decode"].temp_size_in_bytes < 0.05e9
    assert stats["prefill256"].temp_size_in_bytes < 1.8e9
    assert stats["prefill16384"].temp_size_in_bytes < 2.8e9


# -- and for the model that keeps a recurrent state a slot ------------------------
#: Solar-Open2-250B as its cell serves it: one period of four layers (a
#: softmax layer and three KDA layers), 10 of 320 experts, an eighth of
#: the vocabulary, 128 slots of 4,096 positions
KDA_SLOTS, KDA_SEQ, KDA_LAYERS, KDA_HELD = 128, 4096, 3, 10
KDA_PREFILL = (256, 2048)
KDA_SAYS = 6        # what a step's ``out`` holds behind the tokens
KDA_STATE = f"{KDA_SLOTS},64,128,128"
KDA_ROWS = f"{KDA_SLOTS},8,{KDA_SEQ},128"


@pytest.fixture(scope="module")
def kda_programs(topo):
    """name -> compiled program of the engine serving ``solar_open2`` at
    the published widths, lowered from shapes alone."""
    from kungfu_tpu.models.solar_open2 import SolarOpen2, SolarOpen2Config
    from kungfu_tpu.serve.engine import InferenceEngine
    from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec

    cfg = SolarOpen2Config(vocab_size=24576, n_layers=4, init_layers=48,
                           gqa_layers=(0,), experts_held=(0, KDA_HELD),
                           max_seq=KDA_SEQ)
    model = SolarOpen2(cfg)
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    params = jax.tree_util.tree_map(
        shaped, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    spec = PageSpec.for_model(cfg, page_tokens=256)
    # a page counts the softmax layer's rows alone, and is never whole
    assert spec.unpaged and spec.page_bytes == 256 * 8 * 2 * 128 * 2
    eng = InferenceEngine(model, None, max_batch=KDA_SLOTS, max_seq=KDA_SEQ,
                          pool=KVCachePool(spec, capacity_pages=1))
    k, v = jax.tree_util.tree_map(shaped,
                                  jax.eval_shape(eng._caches.new_slabs))
    assert [x.shape for x in jax.tree_util.tree_leaves((k, v))] == [
        (1, KDA_SLOTS, 8, KDA_SEQ, 128)] + [(1, KDA_SLOTS, 64, 128, 128)] * 3 \
        + [(1, KDA_SLOTS, 8, KDA_SEQ, 128)] + [(1, KDA_SLOTS, 3, 24576)] * 3
    ks, vs = jax.tree_util.tree_map(shaped, eng._caches.empty_pages(256))
    slots = jax.ShapeDtypeStruct((KDA_SLOTS,), i32, sharding=one)
    i0 = jax.ShapeDtypeStruct((), i32, sharding=one)
    out = jax.ShapeDtypeStruct((KDA_SLOTS + KDA_SAYS,), i32, sharding=one)
    # the program asks the platform which form its state's update takes
    # (``delta_rule.kda_update_heads``); here it is told what the chip says
    with pytest.MonkeyPatch.context() as steer:
        steer.setattr(jax, "default_backend", lambda: "tpu")
        lowered = {"decode": eng._decode_j.lower(params, k, v, out, slots,
                                                 slots),
                   "restore": eng._restore_j.lower(k, v, ks, vs, i0)}
        for n in KDA_PREFILL:
            lowered[f"prefill{n}"] = eng._prefill_j.lower(
                params, k, v, jax.ShapeDtypeStruct((n,), i32, sharding=one),
                i0, i0, i0)
        assert eng._caches.kda_step_kernel == 1
        assert eng._caches.kv_attn_kernel == 1
    return _compiled("kda", lowered)


def _state_kernels(text):
    """name -> (operands, the operand its first output aliases, op_name)
    of the entry computation's ``kda_step`` kernel calls."""
    entry = text[text.index("\nENTRY"):]
    calls = {}
    for name, operands, rest in re.findall(
            r"^\s*%?([\w.\-]+) = \(f32\[1," + KDA_STATE + r"\]\S*, [^\n]*?\) "
            r"custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\""
            r"([^\n]*)", entry, re.M):
        aliased = re.search(
            r"output_to_operand_aliasing=\{\{0\}: \((\d+), \{\}\)", rest)
        operands = re.findall(r"%([\w.\-]+)", operands)
        calls[name] = (operands, operands[int(aliased.group(1))]
                       if aliased else None, _op_name(text, name))
    return calls


@pytest.mark.parametrize("program", ["decode", "restore"]
                         + [f"prefill{n}" for n in KDA_PREFILL])
def test_hybrid_program_updates_state_and_slab_in_place(kda_programs,
                                                        program):
    """All eight arrays (the rows of K and of V, three layers' states and
    three layers' tails) alias their outputs, and nothing but the
    in-place updates produces an array the size of a slab or of a
    layer's state: no copy of either.  In place is a dynamic update of a
    slice and, in the decode step, the ``kda_step`` kernel whose first
    output aliases the state it was handed -- and nothing else of a
    state's size.  (With ONE state array for the three layers the decode
    step copied the layer it was about to update, 537 MB each:
    serve/recurrent.py.)"""
    assert _LOWERED["kda"][program] == AS_BEFORE["kda"][program]
    text = kda_programs[program].as_text()
    assert len(re.findall(r"may-alias|must-alias",
                          text.split("\n", 1)[0])) == 8
    kernels = _state_kernels(text)
    assert len(kernels) == (KDA_LAYERS if program == "decode" else 0)
    moved = []
    for name, dtype, dims, op in _entry_ops(text):
        in_place = "dynamic-update-slice" in name or "dynamic_update_slice" \
            in name or op == "dynamic-update-slice" \
            or _fused_root(text, name) == "dynamic-update-slice"
        if dims.endswith((KDA_STATE, KDA_ROWS)) and not in_place \
                and op not in ("parameter", "bitcast", "get-tuple-element",
                               "tuple"):
            moved.append((op, name, dtype, dims))
    # (a kernel call's own result is a tuple: the scan above does not see
    # it, and what is taken out of it is a ``get-tuple-element``)
    assert not moved
    for name, (operands, aliased, _) in kernels.items():
        assert aliased is not None and aliased == operands[-1], name


def test_hybrid_decode_has_the_same_operations_whatever_is_live(kda_programs):
    """No operation's SHAPE follows the data (PERF.md, PR 26): ``live``
    reaches the step as a mask, so one compiled program serves every set
    of live slots, and in it nothing loops or branches, no product is
    grouped by the routing, and every KDA layer's state -- all 128 slots
    of it -- is handed to exactly ONE operation: the ``kda_step`` kernel
    under ``attn_core/kda_state``, whose grid is every slot of every
    head block and whose first output aliases the state.  The shapes are
    what stays: the bytes and the time are the live slots' since PR 46
    (a dead slot's grid step names the block the step before it held,
    from the four vectors of scalars the three calls share, and is
    neither copied nor computed), two trips over 1.61 GB only where all
    128 are live."""
    text = kda_programs["decode"].as_text()
    assert "ragged" not in text
    entry = text[text.index("\nENTRY"):]
    assert not re.findall(r"= [^\n]* (while|conditional)\(", entry)
    states = re.findall(
        rf"^\s*%?([\w.\-]+) = f32\[1,{KDA_STATE}\]\S* parameter\(", entry,
        re.M)
    assert len(states) == KDA_LAYERS
    kernels = _state_kernels(text)
    assert len(kernels) == KDA_LAYERS

    def readers(of):
        return re.findall(
            r"^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*? (\w[\w\-]*)\([^\n]*%"
            + re.escape(of) + r"[,)]", entry, re.M)

    seen = set()
    for state in states:
        (name, op), = readers(state)
        assert op == "custom-call" and name in kernels, (state, name, op)
        operands, aliased, op_name = kernels[name]
        assert aliased == state and operands.count(state) == 1
        assert "/attn_core/kda_state/" in op_name and "kda_step" in op_name
        # ``live`` and the plan, the five vectors, the state
        assert len(operands) == 4 + 5 + 1
        seen.add(name)
    assert len(seen) == KDA_LAYERS
    # the plan is made once a step, under the kernel's scope: the three
    # calls are handed the same four vectors of scalars (the later calls
    # through the compiler's own copies between memories)
    def made_by(name):
        while True:
            moved = re.search(r"%?" + re.escape(name) + r" = [^\n]*? "
                              r"copy-(?:done|start)\(%?([\w.\-]+)\)", entry)
            if not moved:
                return name
            name = moved.group(1)

    plans = {tuple(made_by(x) for x in kernels[name][0][:4])
             for name in seen}
    assert len(plans) == 1
    for scalars in plans.pop():
        made, = re.findall(r"%?" + re.escape(scalars) + r" = (\S+) [^\n]*",
                           entry)
        assert made.startswith(f"s32[{KDA_SLOTS}]"), (scalars, made)
    assert "/attn_core/kda_state/" in _op_name(text, scalars)
    # the routed product: one batched product over the ten held experts
    experts_w = re.findall(
        r"%(params__layer_\d____moe____experts____(?:gate|up|down)__[.\d]*) = "
        rf"bf16\[{KDA_HELD},(?:4096,1280|1280,4096)\]", entry)
    assert len(experts_w) == 4 * 3


def test_hybrid_decode_attention_is_one_kernel_over_the_slab(kda_programs):
    """The softmax layer's attention in the decode step is ONE
    ``decode_attn`` kernel under ``attn_core/attn_full`` (the scopes
    ``kv_attn_roofline`` and ``decode_path_ms.attn_full`` read), handed
    the queries through a bitcast and K and V **whole, as the in-place
    row write left them**: no slice, copy or transpose of the slab or of
    its layer, and the scores ``[128, 64, 4096]`` in no type.  Its grid
    is every tile of every slot, so the operations are the same whatever
    is live; which tiles it skips follows the five vectors of scalars it
    is handed first (layer, visible rows, and the walk)."""
    text = kda_programs["decode"].as_text()
    entry = text[text.index("\nENTRY"):]
    calls = re.findall(
        rf"^\s*%?(decode_attn[\w.]*) = bf16\[{KDA_SLOTS},8,8,128\]\S* "
        r"custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"",
        entry, re.M)
    assert len(calls) == 1          # one softmax layer in the period
    (name, operands), = calls
    assert "/attn_core/attn_full/" in _op_name(text, name)
    operands = re.findall(r"%([\w.\-]+)", operands)
    assert len(operands) == 5 + 3
    made_by = {n: (op, dims) for n, _, dims, op in _entry_ops(text)}
    q, k, v = operands[5:]
    assert made_by[q] == ("bitcast", f"{KDA_SLOTS},8,8,128")
    written = set()
    for part in (k, v):     # ... out of the fused in-place row write
        assert made_by[part] == ("get-tuple-element", "1," + KDA_ROWS)
        source = re.search(r"%" + re.escape(part) + r" = [^\n]*"
                           r"get-tuple-element\(%([\w.\-]+)\)", entry).group(1)
        assert "dynamic-update-slice" in source, source
        written.add(source)
    assert len(written) == 1
    # the rows of K and V are read by that kernel and by nothing else
    # after the row write
    for part in (k, v):
        readers = re.findall(
            r"^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*? \w[\w\-]*\([^\n]*%"
            + re.escape(part) + r"[,)]", entry, re.M)
        assert [r for r in readers if not r.startswith("tuple")] == [name], \
            readers
    for scores in (rf"\[{KDA_SLOTS},64,{KDA_SEQ}\]",
                   rf"\[{KDA_SLOTS},8,8,{KDA_SEQ}\]",
                   rf"\[{KDA_SLOTS},8,8,1,{KDA_SEQ}\]"):
        assert not re.search(scores, text), scores


def test_a_stage_of_two_periods_hands_both_kernels_the_slots_rows(topo):
    """Softmax layers 0 and 4 of eight (the cell holds one, at layer 0):
    the decode step holds two ``decode_attn`` calls, handed layer 0 and
    layer 1 of the slab and the SAME four vectors a slot (visible rows
    and the walk) -- not, for the second, whatever the layers between
    left under a name (PR 39's review: the experts' counts)."""
    from kungfu_tpu.models.solar_open2 import SolarOpen2, SolarOpen2Config

    slots, seq = 8, 2048
    cfg = SolarOpen2Config(
        vocab_size=512, d_model=512, n_layers=8, gqa_layers=(0, 4),
        n_heads=16, n_kv_heads=2, head_dim=128, kda_heads=4, gate_rank=16,
        d_expert=128, n_experts=16, experts_held=(0, 16), top_k=4,
        max_seq=seq)
    model = SolarOpen2(cfg)
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    with pytest.MonkeyPatch.context() as steer:
        steer.setattr(jax, "default_backend", lambda: "tpu")
        caches = model.serve_caches(slots, seq)
        params, (k, v) = jax.tree_util.tree_map(shaped, (
            jax.eval_shape(model.init, jax.random.PRNGKey(0)),
            jax.eval_shape(caches.new_slabs)))
        vec = jax.ShapeDtypeStruct((slots,), i32, sharding=one)
        text = jax.jit(caches.decode).lower(
            params, k, v, vec, vec,
            jax.ShapeDtypeStruct((slots,), bool, sharding=one)
        ).compile().as_text()
        assert caches.kv_attn_kernel == 1
    entry = text[text.index("\nENTRY"):]
    calls = re.findall(
        rf"^\s*%?decode_attn[\w.]* = bf16\[{slots},2,8,128\]\S* "
        r"custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"",
        entry, re.M)
    assert len(calls) == 2
    first, second = (re.findall(r"%([\w.\-]+)", c) for c in calls)
    layer = lambda name: re.search(
        r"%" + re.escape(name) + r" = s32\[1\]\S* constant\(\{(\d)\}\)",
        text).group(1)
    assert sorted((layer(first[0]), layer(second[0]))) == ["0", "1"]
    assert first[1:5] == second[1:5]
    made_by = {n: (dtype, dims) for n, dtype, dims, _ in _entry_ops(text)}
    assert all(made_by[n] == ("s32", str(slots)) for n in first[1:5])
    # (K and V are one array for both layers: each call reads it as its
    # own layer's row write left it)
    assert not set(first[6:]) & set(second[6:])


def test_the_other_families_decode_programs_hold_no_such_kernel(
        serve_programs, moe_programs):
    """The dense model's slab has heads of 64 and ``WindowedCaches`` is
    the next issue's: neither decode program holds a custom call."""
    for text in (serve_programs["decode"],
                 moe_programs["decode"].as_text()):
        assert "decode_attn" not in text
        assert "tpu_custom_call" not in text


def test_hybrid_programs_fit_beside_the_weights(kda_programs):
    """2.85 GB of weights and 3.81 GB of cache are arguments of every
    program: the rows of one softmax layer (2.15 GB), three layers' states
    (1.61 GB in float32) and tails (0.06 GB).  A decode step adds some
    0.09 GB -- a copy of one layer's state would be 0.54 -- and a prefill
    of 2,048 tokens 1.1 GB: the decays ``D`` of the chunked recurrence
    exist for one chunk at a time (for all 32 chunks at once they were
    4.3 GB: ops/delta_rule.py)."""
    stats = {n: p.memory_analysis() for n, p in kda_programs.items()}
    cache = 2 * KDA_SLOTS * 8 * KDA_SEQ * 128 * 2 + KDA_LAYERS * KDA_SLOTS \
        * (64 * 128 * 128 * 4 + 3 * 24576 * 2)
    assert cache == 3_814_719_488
    assert stats["restore"].alias_size_in_bytes == cache
    args = stats["decode"].argument_size_in_bytes
    assert 6.6e9 < args < 6.7e9 and 2.85e9 < args - cache < 2.86e9
    assert stats["decode"].temp_size_in_bytes < 0.2e9
    assert stats["prefill256"].temp_size_in_bytes < 0.3e9
    assert stats["prefill2048"].temp_size_in_bytes < 1.5e9


# -- and for the model whose attention reads pooled chunk rows -----------------
#: EvaByte as its cell serves it: one stage of 8 of the 32 layers, every
#: width as published, 16 slots of 32,768 positions -- a slot and layer
#: 2,048 exact rows and 2,048 chunk rows
EVA_SLOTS, EVA_SEQ, EVA_LAYERS = 16, 32768, 8
EVA_SLAB = f"{EVA_LAYERS},{EVA_SLOTS},32,4096,128"
EVA_SAYS = 3        # what a step's ``out`` holds behind the tokens
#: keys a grid step of the decode attention holds: 32 heads of 128, K and
#: V, are 4 MiB at 256 (10.5 MiB by the kernel's own count; 19.75 at 512)
EVA_TILE = 256


@pytest.fixture(scope="module")
def eva_programs(topo):
    """name -> compiled program of the engine serving ``evabyte`` at the
    cell's sizes, lowered from shapes alone: the decode step and the
    prefill of the longest bucket."""
    from kungfu_tpu.models.evabyte import EvaByte, EvaByteConfig
    from kungfu_tpu.serve.engine import InferenceEngine
    from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec

    cfg = EvaByteConfig(n_layers=EVA_LAYERS, init_layers=32, max_seq=EVA_SEQ)
    model = EvaByte(cfg)
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    params = jax.tree_util.tree_map(
        shaped, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    spec = PageSpec.for_model(cfg, page_tokens=2048)
    assert spec.unpaged
    eng = InferenceEngine(model, None, max_batch=EVA_SLOTS, max_seq=EVA_SEQ,
                          pool=KVCachePool(spec, capacity_pages=1))
    k, v = jax.tree_util.tree_map(shaped,
                                  jax.eval_shape(eng._caches.new_slabs))
    assert k.shape == v.shape == (EVA_LAYERS, EVA_SLOTS, 32, 4096, 128)
    slots = jax.ShapeDtypeStruct((EVA_SLOTS,), i32, sharding=one)
    i0 = jax.ShapeDtypeStruct((), i32, sharding=one)
    out = jax.ShapeDtypeStruct((EVA_SLOTS + EVA_SAYS,), i32, sharding=one)
    # the cache asks the platform which form its decode attention takes
    # (``PooledCaches.attn_tile``); here it is told what the chip says
    with pytest.MonkeyPatch.context() as steer:
        steer.setattr(jax, "default_backend", lambda: "tpu")
        lowered = {
            "decode": eng._decode_j.lower(params, k, v, out, slots, slots),
            f"prefill{EVA_SEQ}": eng._prefill_j.lower(
                params, k, v,
                jax.ShapeDtypeStruct((EVA_SEQ,), i32, sharding=one),
                i0, i0, i0)}
        assert eng._caches.attn_tile == EVA_TILE
        assert eng._caches.eva_attn_kernel == 1
    return _compiled("eva", lowered)


def test_pooled_programs_fit_beside_the_weights(eva_programs):
    """3.26 GB of weights and 8.59 GB of cache (16 slots x 8 layers x 64
    MiB) are arguments of every program: 11.85 GB, 74 % of the chip.  A
    decode step adds some 0.04 GB; the prefill of the 32,768 bucket
    walks a window of 2,048 at a time, so its temporaries are a window's
    (the FFN's three intermediates alone would be 2.2 GB for the bucket)
    and stay under the chip's remainder."""
    stats = {n: p.memory_analysis() for n, p in eva_programs.items()}
    cache = 2 * EVA_LAYERS * EVA_SLOTS * 32 * 4096 * 128 * 2
    assert cache == 8_589_934_592 == EVA_SLOTS * EVA_LAYERS * (64 << 20)
    for name, m in stats.items():
        assert m.alias_size_in_bytes == cache, name
        assert 11.85e9 < m.argument_size_in_bytes < 11.86e9, name
    args = stats["decode"].argument_size_in_bytes
    assert 3.26e9 < args - cache < 3.27e9
    assert stats["decode"].temp_size_in_bytes < 0.1e9
    temps = stats[f"prefill{EVA_SEQ}"].temp_size_in_bytes
    assert temps < 1.2e9 and args + temps < 0.85 * 16e9


@pytest.mark.parametrize("program", ["decode", f"prefill{EVA_SEQ}"])
def test_pooled_program_writes_its_slabs_in_place(eva_programs, program):
    """Both slabs alias their outputs, and nothing but the in-place
    updates produces an array the size of a slab or of one layer of one:
    no copy of either, in the decode step or around the prefill's walk
    over the windows."""
    assert _LOWERED["eva"][program] == AS_BEFORE["eva"][program]
    text = eva_programs[program].as_text()
    assert len(re.findall(r"may-alias|must-alias",
                          text.split("\n", 1)[0])) == 2
    moved = []
    for name, dtype, dims, op in _entry_ops(text):
        elems = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
        if elems < EVA_SLOTS * 32 * 4096 * 128 or op in (
                "parameter", "bitcast", "get-tuple-element", "tuple"):
            continue
        in_place = "dynamic-update-slice" in name or "dynamic_update_slice" \
            in name or op in ("dynamic-update-slice", "while") \
            or _fused_root(text, name) == "dynamic-update-slice"
        if not in_place:
            moved.append((op, name, dtype, dims))
    assert not moved


def test_pooled_decode_has_the_same_operations_whatever_the_positions(
        eva_programs):
    """A row written, a chunk completed, a window closed: ``pos`` and
    ``live`` reach the step as masks and as the starts of aligned
    windows, so one compiled program serves every set of positions, and
    in it nothing loops or branches.  Each slot and layer has four
    in-place window updates (the new row and the chunk row, K and V)."""
    text = eva_programs["decode"].as_text()
    entry = text[text.index("\nENTRY"):]
    assert not re.findall(r"= [^\n]* (while|conditional)\(", entry)
    updates = [n for n, _, dims, _ in _entry_ops(text)
               if dims == EVA_SLAB and "dynamic-update-slice" in n]
    assert len(updates) == 4 * EVA_LAYERS * EVA_SLOTS


def test_pooled_decode_attention_is_one_kernel_a_layer_over_the_slab(
        eva_programs):
    """A layer's attention in the decode step is ONE ``decode_attn``
    kernel under ``attn_core/eva_attn`` (the scopes ``eva_attn_roofline``
    and ``decode_path_ms.eva_attn`` read), handed the one query row a
    head padded to eight and K and V **whole, as the in-place row and
    chunk-row writes left them**: no slice, copy or transpose of a slab
    or of its layer, and the scores ``[16, 32, 4096]`` in no type.  Its
    grid is every tile of every slot, so the operations are the same
    whatever is live; which tiles it skips follows the seven vectors of
    scalars it is handed first (layer; the two runs' visible rows; the
    walk: whose block, first and last live tile, the second run's first
    step and its jump), which all eight calls share but for the layer.
    The prefill holds no such kernel."""
    text = eva_programs["decode"].as_text()
    entry = text[text.index("\nENTRY"):]
    calls = re.findall(
        rf"^\s*%?(decode_attn[\w.]*) = bf16\[{EVA_SLOTS},32,8,128\]\S* "
        r"custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"",
        entry, re.M)
    assert len(calls) == EVA_LAYERS == text.count("tpu_custom_call")
    made_by = {n: (op, dtype, dims) for n, dtype, dims, op in
               _entry_ops(text)}
    walks, layers = set(), set()
    for name, operands in calls:
        assert "/attn_core/eva_attn/" in _op_name(text, name)
        operands = re.findall(r"%([\w.\-]+)", operands)
        assert len(operands) == 7 + 3
        assert [made_by[n][1:] for n in operands[:7]] == [("s32", "1")] + [
            ("s32", str(2 * EVA_SLOTS))] + [("s32", str(EVA_SLOTS))] * 3 + [
            ("s32", str(EVA_SLOTS))] * 2
        layers.add(re.search(
            r"%" + re.escape(operands[0]) + r" = s32\[1\]\S* "
            r"constant\(\{(\d)\}\)", text).group(1))
        walks.add(tuple(operands[1:7]))
        q, k, v = operands[7:]
        assert made_by[q][1:] == ("bf16", f"{EVA_SLOTS},32,8,128")
        for part in (k, v):     # ... out of the fused in-place writes
            assert made_by[part][1:] == ("bf16", EVA_SLAB)
            assert "dynamic-update-slice" in part \
                or _fused_root(text, part) == "dynamic-update-slice", part
    assert layers == {str(i) for i in range(EVA_LAYERS)} and len(walks) == 1
    for scores in (rf"\[{EVA_SLOTS},32,4096\]", rf"\[{EVA_SLOTS},32,1,4096\]",
                   rf"\[{EVA_SLOTS},32,8,4096\]"):
        assert not re.search(scores, text), scores
    assert "tpu_custom_call" not in eva_programs[f"prefill{EVA_SEQ}"].as_text()


# -- and for the model with three kinds of content in a slot -----------------
#: Phi-4-mini-flash-reasoning as its cell serves it: all 32 layers and all
#: 200,064 ids, 128 slots of 4,096 positions -- a slot nine Mamba states
#: and tails, eight rings of 512 rows and the one full layer's 4,096
SY_SLOTS, SY_SEQ, SY_PREFILL = 128, 4096, 1024
SY_SAYS = 2         # what a step's ``out`` holds behind the tokens
SY_STATE = f"1,{SY_SLOTS},16,5120"
SY_RING = f"8,{SY_SLOTS},10,512,128"
SY_SLAB = f"1,{SY_SLOTS},10,{SY_SEQ},128"


@pytest.fixture(scope="module")
def sambay_programs(topo):
    """name -> compiled program of the engine serving ``phi4flash`` whole
    at the cell's sizes, lowered from shapes alone: the decode step and
    the prefill of the longest bucket the cell's prompts reach."""
    from kungfu_tpu.models.phi4flash import Phi4Flash, Phi4FlashConfig
    from kungfu_tpu.serve.engine import InferenceEngine
    from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec

    cfg = Phi4FlashConfig(max_seq=SY_SEQ)
    model = Phi4Flash(cfg)
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    params = jax.tree_util.tree_map(
        shaped, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    spec = PageSpec.for_model(cfg, page_tokens=256)
    assert spec.unpaged  # (a page of this family is never whole)
    eng = InferenceEngine(model, None, max_batch=SY_SLOTS, max_seq=SY_SEQ,
                          pool=KVCachePool(spec, capacity_pages=1))
    k, v = jax.tree_util.tree_map(shaped,
                                  jax.eval_shape(eng._caches.new_slabs))
    assert [x.shape for x in jax.tree_util.tree_leaves(k)] == [
        (8, SY_SLOTS, 10, 512, 128), (1, SY_SLOTS, 10, SY_SEQ, 128)] \
        + [(1, SY_SLOTS, 16, 5120)] * 9
    assert [x.shape for x in jax.tree_util.tree_leaves(v)][2:] \
        == [(1, SY_SLOTS, 3, 5120)] * 9
    slots = jax.ShapeDtypeStruct((SY_SLOTS,), i32, sharding=one)
    i0 = jax.ShapeDtypeStruct((), i32, sharding=one)
    out = jax.ShapeDtypeStruct((SY_SLOTS + SY_SAYS,), i32, sharding=one)
    # the cache asks the platform which form its decode attention takes
    # (``SambaYCaches.attn_tiles``); here it is told what the chip says
    with pytest.MonkeyPatch.context() as steer:
        steer.setattr(jax, "default_backend", lambda: "tpu")
        lowered = {
            "decode": eng._decode_j.lower(params, k, v, out, slots, slots),
            f"prefill{SY_PREFILL}": eng._prefill_j.lower(
                params, k, v,
                jax.ShapeDtypeStruct((SY_PREFILL,), i32, sharding=one),
                i0, i0, i0)}
        assert eng._caches.attn_tiles == (512, 512)
        assert eng._caches.kv_attn_kernel == 1
    return _compiled("sambay", lowered)


def test_sambay_programs_fit_beside_the_weights(sambay_programs):
    """7.71 GB of weights (3,852,562,944 parameters in bfloat16) and
    5.78 GB of cache are arguments of every program: 13.49 GB, 84 % of
    the chip's 16 GB.  The cache: eight rings (2 x 128 x 8 x 512 rows of
    2,560 B = 2.68 GB), the one slab (2.68 GB), nine states (0.38 GB in
    float32) and tails.  A decode step adds some 0.14 GB; the prefill of
    1,024 tokens 0.43 GB -- the pairs of the chunked scan exist for one
    chunk of 64 at a time (ops/selective_scan.py), and the cross-decoder
    runs over one row."""
    stats = {n: p.memory_analysis() for n, p in sambay_programs.items()}
    cache = 2 * SY_SLOTS * 10 * 128 * 2 * (8 * 512 + SY_SEQ) \
        + 9 * SY_SLOTS * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert cache == 5_781_585_920
    for name, m in stats.items():
        assert m.alias_size_in_bytes == cache, name
        assert 13.48e9 < m.argument_size_in_bytes < 13.50e9, name
    args = stats["decode"].argument_size_in_bytes
    assert 7.70e9 < args - cache < 7.72e9
    assert stats["decode"].temp_size_in_bytes < 0.25e9
    temps = stats[f"prefill{SY_PREFILL}"].temp_size_in_bytes
    assert temps < 0.7e9 and args + temps < 0.9 * 16e9


@pytest.mark.parametrize("program", ["decode", f"prefill{SY_PREFILL}"])
def test_sambay_program_writes_state_rings_and_slab_in_place(sambay_programs,
                                                             program):
    """All 22 arrays (rings and slab of K and of V, nine layers' states
    and nine layers' tails) alias their outputs; nothing but an in-place
    update produces an array the size of the rings or the slab, and
    nothing COPIES a state in HBM (the decode step's update of a state is
    an elementwise fusion over it, written where it lay; what the
    compiler moves ahead into its fast memory, ``S(1)``, is a prefetch of
    an operand and no second home)."""
    assert _LOWERED["sambay"][program] == AS_BEFORE["sambay"][program]
    text = sambay_programs[program].as_text()
    assert len(re.findall(r"may-alias|must-alias",
                          text.split("\n", 1)[0])) == 22
    moved = []
    for name, dtype, dims, op in _entry_ops(text):
        if op in ("parameter", "bitcast", "get-tuple-element", "tuple"):
            continue
        if dims in (SY_RING, SY_SLAB):
            # (the decode step's row write is ``row_write.py``'s kernel,
            # whose results are its operands' buffers; the prefill's an
            # update in place)
            in_place = "dynamic-update-slice" in name \
                or "dynamic_update_slice" in name \
                or op in ("dynamic-update-slice", "while") \
                or _fused_root(text, name) == "dynamic-update-slice" \
                or (op == "custom-call" and name.startswith("row_write"))
            if not in_place:
                moved.append((op, name, dtype, dims))
        elif dims == SY_STATE and op == "copy":
            moved.append((op, name, dtype, dims))
    assert not moved
    entry = text[text.index("\nENTRY"):]
    homes = re.findall(r"copy-start[\w.]* = \(f32\[" + SY_STATE
                       + r"\](\S*), f32\[" + SY_STATE + r"\](\S*),", entry)
    assert all("S(1)" in a or "S(1)" in b for a, b in homes), homes


def test_sambay_decode_has_the_same_operations_whatever_is_live(
        sambay_programs):
    """``live`` and ``pos`` reach the step as masks and as the kernels'
    scalars: one compiled program serves every set of live slots, and in
    it nothing loops or branches.  A keeping layer has ONE ``row_write``
    call, K and V together, its two results the slabs it was handed
    (eight rings and the slab: nine calls where ``caches.write_rows``
    would be 1,152 window updates and the scalars that find them), and
    the step some 2,300 operations in all."""
    text = sambay_programs["decode"].as_text()
    entry = text[text.index("\nENTRY"):]
    assert not re.findall(r"= [^\n]* (while|conditional)\(", entry)
    assert "dynamic-update-slice" not in entry
    for dims, layers in ((SY_RING, 8), (SY_SLAB, 1)):
        part = r"bf16\[" + dims + r"\]\S*"
        writes = re.findall(
            r"^\s*%?row_write[\w.\-]* = \(" + part + ", " + part
            + r"\) custom-call\(", entry, re.M)
        assert len(writes) == layers, dims
    executed = [op for _, _, _, op in _entry_ops(text) if op not in (
        "parameter", "bitcast", "get-tuple-element", "tuple", "constant")]
    assert len(executed) < 2600
    states = re.findall(
        rf"^\s*%?([\w.\-]+) = f32\[{SY_STATE}\]\S* parameter\(", entry, re.M)
    assert len(states) == 9


def test_sambay_decode_attention_is_one_kernel_a_reading_layer(
        sambay_programs):
    """Sixteen ``decode_attn`` calls and no other kernel that attends
    (nine ``row_write`` calls beside them): eight under
    ``attn_core/attn_window`` over the rings, one under ``attn_full`` and
    seven under ``attn_cross`` over the ONE slab -- all eight handed the
    same K and V, whole, as the full layer's in-place row write left
    them (the scalars of the walk are vectors a slot, which the compiler
    may keep in two places); queries ``[128, 10, 4, 128]`` padded to
    eight rows a pair; the scores ``[128, 40, 4096]`` in no type.  The
    prefill holds no kernel."""
    text = sambay_programs["decode"].as_text()
    entry = text[text.index("\nENTRY"):]
    calls = re.findall(
        rf"^\s*%?(decode_attn[\w.]*) = bf16\[{SY_SLOTS},10,8,128\]\S* "
        r"custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"",
        entry, re.M)
    assert len(calls) == 16 == text.count("tpu_custom_call") - 9
    made_by = {n: (op, dtype, dims) for n, dtype, dims, op in
               _entry_ops(text)}
    by_scope = {}
    for name, operands in calls:
        scope = re.search(r"/attn_core/(attn_\w+)/", _op_name(text, name))
        operands = re.findall(r"%([\w.\-]+)", operands)
        assert len(operands) == 5 + 3
        by_scope.setdefault(scope.group(1), []).append(operands)
    assert {k: len(v) for k, v in by_scope.items()} == {
        "attn_window": 8, "attn_full": 1, "attn_cross": 7}
    full = by_scope["attn_full"] + by_scope["attn_cross"]
    for o in full + by_scope["attn_window"]:
        assert [made_by[n][1:] for n in o[:5]] == [("s32", "1")] + [
            ("s32", str(SY_SLOTS))] * 4
    assert len({tuple(o[6:]) for o in full}) == 1       # one K, one V
    for part in full[0][6:]:
        assert made_by[part][1:] == ("bf16", SY_SLAB)
    rings = by_scope["attn_window"]
    for o in rings:
        assert all(made_by[part][1:] == ("bf16", SY_RING) for part in o[6:])
    layer = lambda name: re.search(
        r"%" + re.escape(name) + r" = s32\[1\]\S* constant\(\{(\d)\}\)",
        text).group(1)
    assert sorted(layer(o[0]) for o in rings) == [str(i) for i in range(8)]
    assert {layer(o[0]) for o in full} == {"0"}
    for scores in (rf"\[{SY_SLOTS},40,{SY_SEQ}\]",
                   rf"\[{SY_SLOTS},10,4,{SY_SEQ}\]",
                   rf"\[{SY_SLOTS},10,4,1,{SY_SEQ}\]",
                   rf"\[{SY_SLOTS},10,8,{SY_SEQ}\]"):
        assert not re.search(scores, text), scores
    assert "tpu_custom_call" not in \
        sambay_programs[f"prefill{SY_PREFILL}"].as_text()
