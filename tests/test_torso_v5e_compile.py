"""The torso's kernels, and the whole fused chunk of the benchmark's torso
configuration, compiled for a described (not attached) TPU v5e at the real
widths: what the chip's compiler refuses, it refuses here, at no chip time.
The topology is described inside a fixture and every such test lives in this
one file (one process may hold the TPU library)."""

import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from d4pg_tpu.learner import D4PGConfig, init_state
from d4pg_tpu.learner.fused import make_fused_chunk
from d4pg_tpu.models import torso as torso_lib
from d4pg_tpu.ops import attention as attn_ops
from d4pg_tpu.ops import sparse_attention as sparse_ops
from d4pg_tpu.replay import device_per as dper
from d4pg_tpu.replay.uniform import TransitionBatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES = 15.75 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def config(monkeypatch):
    with open(os.path.join(
            REPO, "benchmark/configs/humanoid-mellum2-ep4.json")) as f:
        model = json.load(f)["model"]
    # code that asks jax.default_backend() sees the CPU here; the torso
    # picks its kernels by it, so the test answers for the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return D4PGConfig(**model)


def on(sharding, tree):
    return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def call_sites(text, name):
    """The compiled text's lines that call the Pallas kernels whose names
    start with ``name``."""
    return re.findall(
        rf"^\s*%{name}[\w.]* = .*custom_call_target=\"tpu_custom_call\".*$",
        text, re.M)


def kernel_calls(text, name):
    """The compiled program's call sites of the Pallas kernels whose names
    start with ``name`` (the compiler names the call after the kernel)."""
    return len(call_sites(text, name))


@pytest.mark.parametrize("window", [1024, None], ids=["window", "full"])
def test_splash_attention_and_its_backward_compile_at_real_widths(one_chip,
                                                                  window):
    q = jax.ShapeDtypeStruct((1, 4, 8, 4096, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 4096, 128), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        out = attn_ops.causal_attention(q, k, v, window=window,
                                        impl="splash")
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    # forward and backward (full causal: one fused; windowed: dq and dkv)
    assert text.count("tpu_custom_call") >= (2 if window is None else 3)


@pytest.mark.parametrize("impl, kernel", [("megablox", "gmm"),
                                          ("ragged", "ragged-dot")])
def test_the_expert_share_and_its_backward_compile_at_real_widths(
        one_chip, config, impl, kernel):
    spec = config.torso
    d, f, n = spec.hidden_size, spec.moe_intermediate_size, spec.n_held
    stack = lambda a, b: {"kernel": jax.ShapeDtypeStruct(  # noqa: E731
        (n, a, b), jnp.bfloat16)}
    p = {"router": {"kernel": jax.ShapeDtypeStruct((d, spec.num_experts),
                                                   jnp.float32)},
         "gate": stack(d, f), "up": stack(d, f), "down": stack(f, d)}
    h = jax.ShapeDtypeStruct((spec.tokens, d), jnp.float32)

    def loss(p, h):
        out, _counts = torso_lib.expert_share(spec, p, h, jnp.bfloat16, impl)
        return jnp.sum(out)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *on(one_chip, (p, h))).compile().as_text()
    # both buffers are compiled (the usual one and every assignment), each
    # with three products forward and six backward
    assert text.count(kernel) >= 18


def test_the_fused_chunk_of_the_benchmark_cell_fits_the_chip(one_chip,
                                                             config):
    with open(os.path.join(
            REPO, "benchmark/configs/humanoid-mellum2-ep4.json")) as f:
        cfg = json.load(f)
    cap, batch = cfg["replay"]["capacity"], cfg["learner"]["batch_size"]
    state = jax.eval_shape(lambda: init_state(config, jax.random.key(0)))
    trees = jax.eval_shape(lambda: dper.init(cap))
    row = lambda *s: jax.ShapeDtypeStruct((cap,) + s, jnp.float32)  # noqa
    storage = TransitionBatch(
        obs=row(config.obs_dim), action=row(config.act_dim), reward=row(),
        next_obs=row(config.obs_dim), done=row(), discount=row())
    fn = make_fused_chunk(config, k=cfg["learner"]["k"], batch_size=batch)
    size = jax.ShapeDtypeStruct((), jnp.int32)
    compiled = fn.lower(*on(one_chip, (state, trees, storage, size))
                        ).compile()
    m = compiled.memory_analysis()
    # arguments (state 8.64 GB, ring and trees 1.08 GB) are updated in
    # place; temporaries hold the gradient and one sequence of one layer
    assert m.alias_size_in_bytes > 8.6e9
    # the compiler refuses what does not fit; its own count (an upper
    # bound: the buffer assignment packs tighter) stays under the chip's
    held = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.generated_code_size_in_bytes
    assert held < HBM_BYTES, held
    text = compiled.as_text()
    assert "gmm" in text and "splash" in text and "ragged-dot" not in text


# what jax 0.9.0's dynamic-mask kernels read (until PR 46 the backward's):
# the mask's 512 x 512 blocks laid out as int32, 1 GiB a layout
MASK_LAYOUT = "s32[1024,512,512]"
KERNELS = ("group_masked_fwd", "group_masked_dq", "group_masked_dkv")


def vmem_limit(line):
    """The fast memory a Pallas call site asks for, bytes."""
    (limit,) = re.findall(r'"scoped_memory_configs":\[\{"memory_space":"1",'
                          r'"offset":"0","size":"(\d+)"', line)
    return int(limit)


def assert_the_repos_kernels_alone(text):
    """One call site each of this repo's forward, ``dq`` and ``dkv``
    kernels, each on the int8 mask and within ``VMEM_LIMIT``; none of
    jax's splash kernels and no int32 layout of the mask anywhere."""
    for name in KERNELS:
        (site,) = call_sites(text, name)
        assert "s8[16384,16384]" in site and "s32[32,32]" in site
        assert vmem_limit(site) <= sparse_ops.VMEM_LIMIT
    for name in ("splash_mqa_fwd", "splash_mqa_dq", "splash_mqa_dkv"):
        assert kernel_calls(text, name) == 0
    assert MASK_LAYOUT not in text


def _masked(differentiated):
    def out(q, k, v, keep):
        return sparse_ops.masked_attention(q, k, v, keep, impl="splash",
                                           q_chunk=512, kv_chunk=512)

    def loss(q, k, v, keep):
        return jnp.sum(out(q, k, v, keep).astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1, 2)) if differentiated else out


@pytest.fixture(scope="module")
def masked_operands(one_chip):
    """``humanoid-keye2-ep8``'s attention: 16,384 positions, 8 query heads
    a key/value head, the selection an argument."""
    q = jax.ShapeDtypeStruct((4, 8, 16384, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((4, 16384, 128), jnp.bfloat16,
                              sharding=one_chip)
    keep = jax.ShapeDtypeStruct((16384, 16384), jnp.bool_, sharding=one_chip)
    return q, kv, kv, keep


def test_the_kernels_dynamic_mask_form_compiles_at_real_widths(
        masked_operands):
    """Attention under a mask that is an argument, differentiated: this
    repo's three kernels (forward, ``dq``, ``dkv``), each a grid step over
    one int8 tile of the mask for the eight heads; the mask is copied as
    int8 once, for all three, and never laid out as int32 (until PR 46
    jax's backward kernels read two 1 GiB layouts)."""
    compiled = jax.jit(_masked(True)).lower(*masked_operands).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    assert_the_repos_kernels_alone(text)
    # the int8 copy, the output, `lse` and `di`: 0.40 GB (3.9 with the
    # layouts)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


def test_the_forward_alone_lays_out_no_int32_mask(masked_operands):
    """The passes that are not differentiated (1 and 3 of a step, every
    ``train=False`` call): one call of the forward kernel on the int8 mask
    and a 32 x 32 block table, and no second copy of the mask by keys
    (the backward rule's; until PR 43 the forward read an int32 layout: 1
    MB a block and head where this reads 256 KB a block and group)."""
    compiled = jax.jit(_masked(False)).lower(*masked_operands).compile()
    text = compiled.as_text()
    assert kernel_calls(text, "group_masked_fwd") == 1
    assert text.count("tpu_custom_call") == 1
    assert "s32[1024," not in text and "s8[16384,16384]" in text
    assert "s32[32,32]" in text
    # the int8 copy of the mask (268 MB) and little else
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def test_a_sparse_attention_layer_and_its_backward_compile_at_real_widths(
        one_chip, monkeypatch):
    """One ``sparse_attention`` layer of ``humanoid-keye2-ep8`` on one
    16,384-token sequence, differentiated: index scores, the threshold
    selection, the alignment loss, the kernel under the selection and the
    expert layer 4,096 tokens at a time, in the memory the chip has beside
    8.4 GiB of state and ring. (The whole chunk of that cell compiles in
    ~3 minutes here: PR 32's builder did it by hand, not this suite.)"""
    with open(os.path.join(
            REPO, "benchmark/configs/humanoid-keye2-ep8.json")) as f:
        model = json.load(f)["model"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = D4PGConfig(**model)
    torso = config.build_critic().torso
    assert torso.sparse_impl() == "splash" \
        and torso.grouped_impl() == "megablox"
    params = jax.eval_shape(lambda: torso.init(jax.random.key(0)))["layer_0"]
    x = jax.ShapeDtypeStruct((1, 16384, 2048), jnp.float32)

    def loss(p, x):
        out, _stats, (_selected, index_loss) = torso._layer(
            p, x, "sparse_attention", False, True)
        return jnp.sum(out) + jnp.sum(index_loss)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *on(one_chip, (params, x))).compile()
    text = compiled.as_text()
    assert "gmm" in text and "ragged-dot" not in text
    # the every-assignment buffer is a 4,096-token part's, not a sequence's
    assert "[131072,2048]" not in text and "[32768,2048]" in text
    # the attention's forward runs ONCE a forward evaluation: that call's
    # output, its backward's residual and the alignment target's
    # log-sum-exp are one call's. Nothing reads the values of the pass going
    # up here, so the program holds the rematerialised evaluation alone: 1
    # (2 until PR 42, when the loss made a pass of its own; the cell's
    # chunk, which also holds passes 1 and 3, 6 -> 4 a layer). Since PR 43
    # it is this repo's kernel on the int8 mask, and since PR 46 so are the
    # backward's two: no kernel of jax's splash module, no int32 layout
    assert_the_repos_kernels_alone(text)
    # 5.98 GB (6.48 with the second pass)
    assert compiled.memory_analysis().temp_size_in_bytes < 6.3e9


@pytest.mark.parametrize("index, layer_type, kernels", [
    (0, "conv", ()), (1, "full_attention", ("splash", "gmm")),
    (2, "conv", ("gmm",))], ids=["conv-dense", "attention-experts",
                                 "conv-experts"])
def test_each_kind_of_lfm2_layer_and_its_backward_compile_at_real_widths(
        one_chip, monkeypatch, index, layer_type, kernels):
    """One layer of ``humanoid-lfm2-ep4`` on one 8,192-token sequence,
    differentiated: the short convolution's projections, gates and taps and
    the dense feed-forward of 7,168 (plain XLA, no kernel); the splash kernel
    at 8 key/value heads of 64 with 4 queries each; the grouped products at
    2048 x 1792, the first whose tile does not hold K whole, 4,096 tokens at
    a time. (The whole chunk of that cell compiles in ~80 s here: PR 34's
    builder did it by hand, not this suite.)"""
    with open(os.path.join(
            REPO, "benchmark/configs/humanoid-lfm2-ep4.json")) as f:
        model = json.load(f)["model"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = D4PGConfig(**model)
    torso = config.build_critic().torso
    assert torso.attention_impl() == "splash" \
        and torso.grouped_impl() == "megablox"
    params = jax.eval_shape(lambda: torso.init(jax.random.key(0)))[
        f"layer_{index}"]
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.float32)
    dense = index < config.torso.num_dense_layers

    def loss(p, x):
        out, _stats, _selected = torso._layer(p, x, layer_type, dense, True)
        return jnp.sum(out)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *on(one_chip, (params, x))).compile()
    text = compiled.as_text()
    for kernel in ("splash", "gmm"):
        assert (kernel in text) == (kernel in kernels), kernel
    assert "ragged-dot" not in text
    if "gmm" in kernels:  # a 4,096-token part's every-assignment buffer
        assert "[16384,2048]" in text and "[32768,2048]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9


# the gated delta rule's solve as the compiled text shows it: the eight
# 8-token diagonal blocks of a group's 4 chunks of 32 heads stacked with
# that batch of 128 on the lanes, and what a block a ``[8, 8]`` lane tile
# of its own (the parent's layout) would read
SOLVE_BLOCKS, PADDED_BLOCKS = "f32[8,8,8,128]", "f32[4,32,8,8]"


def test_the_delta_rule_scan_and_its_backward_compile_at_real_widths(
        one_chip):
    """``ops/delta_rule.gated_delta_rule`` on one 16,384-token sequence of
    ``humanoid-qwen3next-ep32`` (16 key heads serving 32 value heads of
    128), differentiated in all five inputs: 256 chunks in 64 rematerialised
    groups, so two nested loops forward and again backward, the solve inside
    a chunk products like the rest (no ``triangular-solve`` custom call),
    its blocks stacked with a group's 4 x 32 chunks and heads on the lanes
    (no block a lane tile of its own); its temporaries are a group's, not
    the sequence's (every chunk's state kept would alone be 537 MB)."""
    from d4pg_tpu.ops import delta_rule

    f32 = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.float32, sharding=one_chip)
    t_len, hk, hv, d = 16384, 16, 32, 128

    def loss(q, k, v, g, beta):
        return jnp.sum(delta_rule.gated_delta_rule(q, k, v, g, beta))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        f32(t_len, hk, d), f32(t_len, hk, d), f32(t_len, hv, d),
        f32(t_len, hv), f32(t_len, hv)).compile()
    text = compiled.as_text()
    assert text.count(" while(") >= 4 and "triangular" not in text
    assert "tpu_custom_call" not in text  # plain XLA: no kernel
    assert SOLVE_BLOCKS in text and PADDED_BLOCKS not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def test_splash_attention_compiles_at_256_wide_heads_and_two_kv_heads(
        one_chip):
    """The gated attention of ``humanoid-qwen3next-ep32``: 8 query heads on
    each of 2 key/value heads of 256 at 16,384 positions, full causal, the
    first heads here wider than 128: forward and backward kernels."""
    q = jax.ShapeDtypeStruct((1, 2, 8, 16384, 256), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2, 16384, 256), jnp.bfloat16,
                              sharding=one_chip)
    assert attn_ops.splash_fits(16384, 256)

    def loss(q, k, v):
        out = attn_ops.causal_attention(q, k, v, window=None, impl="splash")
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


# the layer alone: 6.23 and 4.25 GB (6.25 and 4.26 until PR 50; 6.55 and
# 4.78 where ``route`` hands its weights on without its barrier: the same
# peak of live bytes, 5.02 GB, in a heap packed worse)
@pytest.mark.parametrize("index, layer_type, kernels, temp", [
    (0, "linear_attention", ("gmm",), 6.5e9),
    (3, "full_attention", ("splash", "gmm"), 4.6e9)],
    ids=["deltanet-experts", "gated-attention-experts"])
def test_each_kind_of_qwen3next_layer_and_its_backward_compile_at_real_widths(
        one_chip, monkeypatch, index, layer_type, kernels, temp):
    """One layer of ``humanoid-qwen3next-ep32`` on one 16,384-token
    sequence, differentiated: the Gated DeltaNet operator (projections of
    12,288 and 64 outputs, four taps on 8,192 channels, the scan, the gated
    output norm) or the output-gated attention (the splash kernel at 2
    key/value heads of 256 with 8 queries each, a quarter of a head
    rotated), then the router's 512 outputs and ten choices a token, the
    grouped products at 2048 x 512 over 16 held experts 4,096 tokens at a
    time, and the shared expert."""
    with open(os.path.join(
            REPO, "benchmark/configs/humanoid-qwen3next-ep32.json")) as f:
        model = json.load(f)["model"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = D4PGConfig(**model)
    torso = config.build_critic().torso
    assert torso.attention_impl() == "splash" \
        and torso.grouped_impl() == "megablox"
    params = jax.eval_shape(lambda: torso.init(jax.random.key(0)))[
        f"layer_{index}"]
    x = jax.ShapeDtypeStruct((1, 16384, 2048), jnp.float32)

    def loss(p, x):
        out, _stats, _selected = torso._layer(p, x, layer_type, False, True)
        return jnp.sum(out)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *on(one_chip, (params, x))).compile()
    text = compiled.as_text()
    for kernel in ("splash", "gmm"):
        assert (kernel in text) == (kernel in kernels), kernel
    assert "ragged-dot" not in text
    # a 4,096-token part's every-assignment buffer: ten rows a token
    assert "[40960,2048]" in text and "[163840,2048]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < temp


def test_the_routing_of_the_linear_cell_sorts_no_experts_and_scatters_no_scalars(
        one_chip):
    """``route`` and the sorted buffer's places with their gradient at a
    4,096-token part of ``humanoid-qwen3next-ep32`` (``[4096, 2048] x [2048,
    512]``, ten of 512 experts a token, 16 held), alone: the selection is
    dense passes over ``[4096, 512]``, not a sort of every token's 512
    experts; its cotangent is placed by a compare and a sum, not a scatter
    of 40,960 scalars into the ``[4096 x 512]`` (on the chip 200-360 us a
    part, and booked by a trace under no scope of the torso's); the places
    are a cumulative sum, not a scatter into ``s32[40960]`` (all three were
    in the chunk until PR 50)."""
    with open(os.path.join(
            REPO, "benchmark/configs/humanoid-qwen3next-ep32.json")) as f:
        spec = D4PGConfig(**json.load(f)["model"]).torso
    lo, hi = spec.experts_held
    t_len, k = torso_lib.EXPERT_TOKENS, spec.num_experts_per_tok
    f32 = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.float32, sharding=one_chip)

    def loss(h, kernel, weigh):
        with jax.named_scope("torso.route"):
            w, e, stats = torso_lib.route(spec, h, {"kernel": kernel})
            places = torso_lib._places(e, lo, stats["route_counts"][lo:hi])
        return jnp.sum(w * weigh), (e, places, stats)

    text = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True)).lower(
        f32(t_len, spec.hidden_size), f32(spec.hidden_size, spec.num_experts),
        f32(t_len, k)).compile().as_text()
    routed = [line for line in text.splitlines() if "torso.route" in line]
    assert len(routed) > 20  # the scope's name reaches the compiled text
    wide = f"[{t_len},{spec.num_experts}]"
    sorts = [line for line in routed if " sort(" in line]
    assert not [line for line in sorts if wide in line], sorts
    # a scatter's metadata may not name the scope: every line counts
    scatters = [line for line in text.splitlines() if " scatter(" in line]
    for result in (f"f32[{t_len * spec.num_experts}]", f"f32{wide}",
                   f"s32[{t_len * k}]"):
        assert not [line for line in scatters
                    if line.split(" scatter(")[0].count(result)], result


def test_the_fused_chunk_of_the_linear_cell_compiles_for_the_chip(
        one_chip, monkeypatch):
    """The whole chunk of ``humanoid-qwen3next-ep32`` at the cell's sizes (2
    sequences of 16,384 tokens, K=1, a 16,384-row ring): the compiler
    refuses a program that does not fit the chip, and takes this one. Its
    own count of arguments and temporaries together (8.34 + 8.69 GB) is
    over ``HBM_BYTES`` as the chunks of ``humanoid-lfm2-ep4`` (10.30 + 7.01)
    and ``humanoid-keye2-ep8`` (8.99 + 9.99) are, which run: the buffer
    assignment packs tighter than the sum."""
    with open(os.path.join(
            REPO, "benchmark/configs/humanoid-qwen3next-ep32.json")) as f:
        cfg = json.load(f)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = D4PGConfig(**cfg["model"])
    cap = cfg["replay"]["capacity"]
    row = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        (cap,) + s, jnp.float32, sharding=one_chip)
    storage = TransitionBatch(
        obs=row(config.obs_dim), action=row(config.act_dim), reward=row(),
        next_obs=row(config.obs_dim), done=row(), discount=row())
    compiled = _chunk(cfg, config, storage, one_chip)
    m = compiled.memory_analysis()
    # the state (6.19 GB of parameters, moments and targets) in place
    assert m.alias_size_in_bytes > 6.1e9
    assert m.argument_size_in_bytes < 8.5e9 and m.temp_size_in_bytes < 9.2e9
    text = compiled.as_text()
    assert "gmm" in text and "splash" in text
    assert "ragged-dot" not in text and "triangular" not in text
    assert SOLVE_BLOCKS in text and PADDED_BLOCKS not in text


def _loop_body(text):
    """The lines of the (one) ``while`` loop's body in compiled text."""
    import re

    body = re.search(r"\bwhile\(.*?body=%?([\w.\-]+)", text).group(1)
    return re.search(r"^%?" + re.escape(body) + r" [^\n]*\{\n(.*?)^\}", text,
                     re.S | re.M).group(1).splitlines()


@pytest.mark.parametrize("batch", [256, 4096], ids=["chunk", "commit"])
def test_the_tree_repair_reads_rows_and_kept_levels_only_on_the_chip(
        one_chip, batch):
    """``set_leaves`` at the humanoid-mlp cells' shapes (2,097,152 leaves;
    the chunk's B = 256 and the commit's 4,096) inside a scan over donated,
    loop-carried trees, compiled for the v5e. The chunk's body (21 > 14 by
    rows, then whole) holds no ``copy`` of a 16 MB tree and fourteen
    ``reduce-window``s, the widest over level 14 (``[128, 128]``, 64 KB a
    tree, both trees in each), and leaves the sum tree in HBM: nothing
    moves its 16 MB into ``S(1)`` and out again a step, as the parent's
    whole-level windows made the compiler do (PR 35, PR 37). The commit's
    (whole at every step) holds twenty-one, the widest over the leaves:
    one kept level's span. Either holds ONE scatter of the leaves (PR 39:
    the min tree has none; its 128 KB are the only other tree array, and
    no operation makes a second array of the sum tree's size but the sum
    tree's own scatter and slice updates)."""
    import re

    cap = 1 << 21

    def loop(trees, idx, td):
        def body(trees, x):
            return dper.update_from_td(trees, x[0], x[1], 0.6), None
        return jax.lax.scan(body, trees, (idx, td))[0]

    trees = on(one_chip, jax.eval_shape(lambda: dper.init(cap)))
    lines = _loop_body(jax.jit(loop, donate_argnums=(0,)).lower(
        trees,
        jax.ShapeDtypeStruct((4, batch), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((4, batch), jnp.float32, sharding=one_chip),
    ).compile().as_text())
    whole_tree = re.compile(r"= f32\[%d\]\S* copy\(" % (2 * cap))
    assert not [ln for ln in lines if whole_tree.search(ln)][:2]
    leaf_scatters = [ln for ln in lines if re.search(
        r"= f32\[%d\]\S* (fusion|scatter)\(.*writeback\.leaves/scatter"
        % (2 * cap), ln)]
    assert len(leaf_scatters) == 1
    assert trees.min_tree.shape == (2 * cap // 128,)
    windows = [tuple(map(int, m.groups())) for ln in lines for m in [
        re.search(r"= \(f32\[(\d+),(\d+)\]\S*, f32\[\d+,\d+\]\S*\) "
                  r"reduce-window\(", ln)] if m]
    assert len(windows) == sum(" reduce-window(" in ln for ln in lines)
    if batch == 256:
        assert len(windows) == 14 and max(windows) == (128, 64)
        in_fast_memory = re.compile(r"= f32\[%d\]\{[^}]*S\(1\)" % (2 * cap))
        assert not [ln for ln in lines if in_fast_memory.search(ln)][:2]
        assert not [ln for ln in lines if " copy-done(" in ln
                    and "f32[%d]" % (2 * cap) in ln.split(" copy-done(")[0]]
    else:
        assert len(windows) == 21 and max(windows) == (cap >> 7, 64)


@pytest.mark.parametrize("levels, batch", [(21, 256), (16, 512)],
                         ids=["mlp_cells", "pixel_cell"])
def test_the_descent_reads_rows_of_the_tree_in_place_on_the_chip(
        one_chip, levels, batch):
    """``descend`` reads the sum tree as ``[2N / 128, 128]`` rows: the same
    bytes under the chip's tilings (``T(1024)`` and ``T(8,128)``), so the
    compiler must answer the view with a bitcast, not with a copy of the
    tree a step. Sample, weights and write-back in a scan over donated,
    loop-carried trees at the cells' shapes: nothing in the loop body makes
    a whole tree but the write-back (PR 37), each in place:

    - MLP cells (21 > 14 by rows, then whole): four fusions, the sum
      tree's leaf scatter and three slice updates (level 14, scattered
      into as a 64 KB slice of its own; level 7; node 1);
    - pixel cell (whole at every step: 16 > 9 > 2 > root): four, the leaf
      scatter and three slice updates (levels 9 and 2, node 1);

    the parent had eight, the same again for a min tree of the sum
    tree's size (PR 39: the min tree has no leaves and is 1/128 of it);

    and where the compiler moves a tree through ``S(1)`` (the pixel
    cell's windows) the moves are ``copy-done`` and a ``ConcatBitcast``
    of parts, none of them a ``copy``. Sampling gathers from the tree
    twice by the row and once by the leaf, where the level-by-level walk
    gathered ``levels + 1`` times (PR 35); the write-back reads no leaf
    back and, by rows, gathers the touched rows of the sum tree's leaves
    ONCE, for both trees."""
    import re

    cap = 1 << levels

    def loop(trees, key, size):
        def body(carry, _):
            trees, key = carry
            key, k = jax.random.split(key)
            idx = dper.sample(trees, k, batch, size)
            w = dper.is_weights(trees, idx, jnp.float32(0.5), size)
            return (dper.update_from_td(trees, idx, w, 0.6), key), idx
        return jax.lax.scan(body, (trees, key), None, length=4)

    trees = on(one_chip, jax.eval_shape(lambda: dper.init(cap)))
    lines = _loop_body(jax.jit(loop, donate_argnums=(0,)).lower(
        trees,
        jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    ).compile().as_text())
    made = re.compile(r"= f32\[(?:%d|%d,128)\]\S* ([\w\-]+)\("
                      % (2 * cap, 2 * cap // 128))
    whole = [(m.group(1), ln) for ln in lines for m in [made.search(ln)] if m]
    ops = [op for op, _ln in whole]
    assert ops.count("fusion") == 4 and "copy" not in ops
    assert set(ops) <= {"fusion", "get-tuple-element", "bitcast",
                        "copy-done", "custom-call"}
    assert all("ConcatBitcast" in ln for op, ln in whole
               if op == "custom-call")  # a move into S(1), in parts
    gathers = [ln.split(" fusion(")[0] for ln in lines
               if re.search(r'kind=kCustom.*op_name="[^"]*/gather"', ln)]
    by_rows = dper.repair_plan(cap, batch)[0][2] == "rows"
    assert by_rows == (levels == 21)
    assert sorted(g.split("= ")[1].split("{")[0] for g in gathers) == [
        "f32[%d,128]" % batch] * (2 + by_rows) + ["f32[%d]" % batch]


# configuration: the wide ring field's type as the compiled text prints it
# (rows filled in), its pinned XLA layout, the bounds on the program's
# temporaries and on the commit's aliased bytes, and what the chunk may
# still make of a NARROW field of the ring's row count: the pixel chunk
# copies its 6-wide actions rows-major (1 MB read; the parent's does too);
# the MLP commit's bound lies between the ring alone (6.682 GB) and the
# ring with the 16.8 MB sum tree (the min tree is 128 KB since PR 39);
# and how ``train``'s ``plan:`` line spells the pin
RING_CELLS = {
    "humanoid-mlp": dict(wide="f32[%d,376]", layout="{1,0:T(8,128)}",
                         temp=0.5e9, alias=6.69e9, narrow=(), plan="01"),
    "dmc-pixels-drq": dict(wide="u8[%d,84,84,9]",
                           layout="{2,1,3,0:T(8,128)(4,1)}",
                           temp=1e9, alias=8.1e9, narrow=("copy",),
                           plan="0312"),
}


def _ring_programs(name, one_chip, capacity=None):
    """The configuration (``capacity`` in place of its own), its
    ``D4PGConfig``, and its ring as ``DeviceStore`` would describe it on
    the described chip: specs, formats, abstract arrays."""
    from benchmark.cellbuild import learner_config, row_spec
    from d4pg_tpu.replay.device_ring import ring_formats, ring_specs

    with open(os.path.join(REPO, "benchmark/configs", name + ".json")) as f:
        cfg = json.load(f)
    if capacity:
        cfg["replay"]["capacity"] = capacity
    config = learner_config(cfg)
    specs = ring_specs(
        cfg["replay"]["capacity"] + cfg["replay"]["block_rows"],
        row_spec(cfg, config)["obs_shape"], config.act_dim,
        jnp.uint8 if config.pixels else jnp.float32)
    formats = ring_formats(specs, one_chip)
    assert [f is not None for f in formats] == [True, False, False,
                                                True, False, False]
    storage = TransitionBatch(*[
        jax.ShapeDtypeStruct(shape, dtype, sharding=fmt or one_chip)
        for (shape, dtype), fmt in zip(specs, formats)])
    return cfg, config, specs, formats, storage


def _chunk(cfg, config, storage, one_chip):
    state = on(one_chip, jax.eval_shape(
        lambda: init_state(config, jax.random.key(0))))
    trees = on(one_chip, jax.eval_shape(
        lambda: dper.init(cfg["replay"]["capacity"])))
    fn = make_fused_chunk(config, k=cfg["learner"]["k"],
                          batch_size=cfg["learner"]["batch_size"])
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return fn.lower(state, trees, storage, i32).compile()


@pytest.mark.parametrize("program", ["jit_fn", "jit_commit"])
@pytest.mark.parametrize("name", sorted(RING_CELLS, reverse=True))
def test_the_cells_programs_take_the_ring_as_it_is_stored(one_chip, name,
                                                          program):
    """The ``humanoid-mlp`` and ``dmc-pixels-drq`` cells' chunk and block
    commit at the real shapes, ring fields in the formats ``DeviceStore``
    gives them on a TPU, compiled for the v5e (PR 31, 33). The chunk
    gathers from the parameter itself: the compiler's own layout for
    ``f32[2101248, 376]`` has the rows on the lanes and for
    ``u8[40256, 84, 84, 9]`` the frames, and the parents' chunks re-laid
    both wide fields whole, once a dispatch (two ``copy`` to
    ``bf16[2101248,376]{1,0}``, 3.33 GB of temporaries, 14.4 of 26.7 ms;
    two ``copy`` to ``u8[40256,84,84,9]{2,1,3,0}``, 8.57 GB, 31.9 of 91.5
    ms). The commit writes its block into the donated ring in place and
    returns it in the same formats."""
    import re

    from d4pg_tpu.replay.fused_buffer import make_commit

    want = RING_CELLS[name]
    cfg, config, specs, formats, storage = _ring_programs(name, one_chip)
    cap, block = cfg["replay"]["capacity"], cfg["replay"]["block_rows"]
    rows = cap + block
    if program == "jit_fn":
        compiled = _chunk(cfg, config, storage, one_chip)
        in_place = ()
    else:
        trees = on(one_chip, jax.eval_shape(lambda: dper.init(cap)))
        i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        frame = TransitionBatch(*[
            jax.ShapeDtypeStruct((block,) + shape[1:], dtype,
                                 sharding=one_chip) for shape, dtype in specs])
        fn = make_commit(cap, block, cfg["learner"]["per_alpha"], formats)
        compiled = fn.lower(storage, trees, frame, i32, i32).compile()
        # the block lands by dynamic-update-slice (alone or fused) on the
        # donated ring; anything else of the ring's size is a copy of it
        in_place = ("dynamic-update-slice", "fusion")
    text = compiled.as_text()
    assert text.startswith("HloModule " + program)
    # (c) the program receives the wide fields as the gather reads them
    wide = want["wide"] % rows
    for field in ("obs", "next_obs"):
        assert re.search(r"storage_%s\S* = %s parameter\(" % (
            field, re.escape(wide + want["layout"])), text), field
    # (a) nothing of the ring's row count and rank >= 2 is computed
    whole = re.compile(r"^\s*(?:ROOT )?%%?\S+ = \(?([a-z0-9]+\[%d,[\d,]+\])\S* "
                       r"([\w\-]+)\(" % rows)
    made = {m.groups() for m in map(whole.match, text.splitlines()) if m}
    passed = {"parameter", "get-tuple-element", "bitcast", "tuple", *in_place}
    assert (wide, "parameter") in made
    assert {op for field, op in made if field == wide} <= passed, made
    assert {op for field, op in made if field != wide} \
        <= passed | set(want["narrow"]), made
    m = compiled.memory_analysis()
    # (b) no second ring among the temporaries
    assert m.temp_size_in_bytes < want["temp"], m.temp_size_in_bytes
    if in_place:  # the whole ring and both trees are aliased
        assert m.alias_size_in_bytes > want["alias"], m.alias_size_in_bytes


@pytest.mark.parametrize("name", sorted(RING_CELLS))
def test_the_plan_line_names_what_each_field_is_pinned_to(one_chip, name):
    from d4pg_tpu.train import _layouts

    pin = RING_CELLS[name]["plan"]
    formats = _ring_programs(name, one_chip)[3]
    assert _layouts(formats) == (f"obs:{pin},action:-,reward:-,"
                                 f"next_obs:{pin},done:-,discount:-")


def test_the_pixel_chunk_fits_the_chip_at_70256_rows(one_chip):
    """What the pin buys the ring's size: with no second padded copy among
    the temporaries the pixel chunk compiles at 70,256 rows (14.39 GB of
    arguments, 0.42 GB of temporaries) where 40,256 was the most the
    parent's program fitted (14.1 GB). DrQ's 100,000 frames would be 20.3
    GB pinned: they need the flat form (PERF.md section 7)."""
    cfg, config, _specs, _formats, storage = _ring_programs(
        "dmc-pixels-drq", one_chip, capacity=70000)
    assert storage.obs.shape[0] == 70256
    m = _chunk(cfg, config, storage, one_chip).memory_analysis()
    assert m.temp_size_in_bytes < 1e9, m.temp_size_in_bytes
    held = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.generated_code_size_in_bytes
    assert 14e9 < held < HBM_BYTES, held


def _ouro(monkeypatch):
    with open(os.path.join(
            REPO, "benchmark/configs/humanoid-ouro-ut4.json")) as f:
        cfg = json.load(f)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return cfg, D4PGConfig(**cfg["model"])


def test_an_ouro_layer_and_its_backward_compile_at_real_widths(
        one_chip, monkeypatch):
    """One layer of ``humanoid-ouro-ut4`` on one 4,096-token sequence,
    differentiated: the first ungrouped attention (16 query heads on 16
    key/value heads of 128) through the splash kernel, the dense SwiGLU of
    5,632 and the four norms of a layer, with no grouped product anywhere
    (a torso without experts never asks for ``grouped_impl``)."""
    _cfg, config = _ouro(monkeypatch)
    torso = config.build_critic().torso
    assert attn_ops.splash_fits(4096, 128)
    assert torso.attention_impl() == "splash"
    params = jax.eval_shape(lambda: torso.init(jax.random.key(0)))
    assert set(params["layer_0"]) >= {"op_post_norm", "ff_post_norm"}
    x = jax.ShapeDtypeStruct((1, 4096, 2048), jnp.float32)

    def loss(p, x):
        out, stats, _selected = torso._layer(p, x, "full_attention", True,
                                             True)
        assert stats == {}
        return jnp.sum(out)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *on(one_chip, (params["layer_0"], x))).compile()
    text = compiled.as_text()
    assert "splash" in text and text.count("tpu_custom_call") >= 2
    assert "gmm" not in text and "ragged-dot" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


def test_the_fused_chunk_of_the_loop_cell_compiles_for_the_chip(
        one_chip, monkeypatch):
    """The whole chunk of ``humanoid-ouro-ut4`` at the cell's sizes (2
    sequences of 4,096 tokens, K=1, a 32,768-row ring, 4 passes of 8
    layers): the compiler refuses a program that does not fit the chip, and
    takes this one. One pass is compiled, not four: the loop over passes is
    a ``while`` of its own in each of the three torso passes and in the
    backward pass, beside the loops over sequences."""
    cfg, config = _ouro(monkeypatch)
    cap = cfg["replay"]["capacity"]
    row = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        (cap,) + s, jnp.float32, sharding=one_chip)
    storage = TransitionBatch(
        obs=row(config.obs_dim), action=row(config.act_dim), reward=row(),
        next_obs=row(config.obs_dim), done=row(), discount=row())
    compiled = _chunk(cfg, config, storage, one_chip)
    m = compiled.memory_analysis()
    # the state (8.21 GB of parameters, moments and targets) in place
    assert m.alias_size_in_bytes > 8.2e9
    assert m.argument_size_in_bytes < 9.4e9 and m.temp_size_in_bytes < 10.5e9
    text = compiled.as_text()
    assert "splash" in text and "gmm" not in text
    assert "ragged-dot" not in text
    assert "torso.exit" in text and "torso.mlp" in text
    # eight layers a pass, forward three times and once more with its
    # backward: a pass traced four times would hold four times as many
    assert 32 <= text.count("tpu_custom_call") <= 64


def _nemotronh_spec(monkeypatch):
    with open(os.path.join(
            REPO, "benchmark/configs/humanoid-nemotronh-ep16.json")) as f:
        block = json.load(f)["model"]["torso"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return torso_lib.TorsoSpec.from_dict(block)


def test_the_ssd_scan_and_its_backward_compile_at_real_widths(one_chip):
    """``ops/ssd.py`` at Nemotron-H's sizes (8,192 tokens, 64 heads of 64, a
    ``[64, 128]`` state, 8 groups), bfloat16 products, forward and gradients:
    plain XLA products and one scan over groups of chunks, no kernel."""
    from d4pg_tpu.ops import ssd as ssd_ops

    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,  # noqa: E731
                                          sharding=one_chip)
    args = (f32(8192, 64, 64), f32(8192, 64), f32(64), f32(8192, 8, 128),
            f32(8192, 8, 128), f32(64))

    def loss(x, dt, a, b, c, d):
        return jnp.sum(ssd_ops.ssd(x, dt, a, b, c, d, dtype=jnp.bfloat16))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        *args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text and "while" in text
    # a state a group of 4 chunks is kept (16 x 2 MB), not one a chunk, and
    # nothing chunk-by-chunk-by-head square outlives its group
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def test_relu2_experts_of_1856_reach_megablox_padded(one_chip, monkeypatch):
    """1,856 is 14.5 x 128: the grouped products go to the megablox kernels
    zero-padded to 1,920 (``ops/grouped.py``), not to XLA's ``ragged_dot``;
    two products an expert forward and four backward, in both buffers."""
    spec = _nemotronh_spec(monkeypatch)
    torso = torso_lib.build_torso(spec, jnp.bfloat16)
    assert torso.grouped_impl() == "megablox"
    assert torso.attention_impl() == "splash"
    d, f, n = spec.hidden_size, spec.moe_intermediate_size, spec.n_held
    fs = spec.shared_expert_intermediate_size
    bf = lambda *s: {"kernel": jax.ShapeDtypeStruct(s, jnp.bfloat16)}  # noqa
    p = {"router": {"kernel": jax.ShapeDtypeStruct((d, spec.num_experts),
                                                   jnp.float32),
                    "bias": jax.ShapeDtypeStruct((spec.num_experts,),
                                                 jnp.float32)},
         "up": bf(n, d, f), "down": bf(n, f, d), "shared_up": bf(d, fs),
         "shared_down": bf(fs, d)}
    h = jax.ShapeDtypeStruct((torso_lib.EXPERT_TOKENS, d), jnp.float32)

    def loss(p, h):
        out, _stats = torso_lib.expert_share(spec, p, h, jnp.bfloat16,
                                             "megablox")
        return jnp.sum(out)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *on(one_chip, (p, h))).compile().as_text()
    assert text.count("gmm") >= 12 and "ragged-dot" not in text
    assert "1920" in text


def test_sixteen_query_heads_a_key_value_head_go_to_the_splash_kernel(
        one_chip):
    """Nemotron-H's attention block: 32 query heads on 2 key/value heads of
    128 at 8,192 tokens, one group of 16 a kernel call, forward and
    backward."""
    q = jax.ShapeDtypeStruct((1, 2, 16, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2, 8192, 128), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        out = attn_ops.causal_attention(q, k, v, window=None, impl="splash")
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") >= 2


def test_trinitys_windowed_gated_attention_compiles_at_real_widths(
        one_chip, monkeypatch):
    """The attention of one sliding layer of ``humanoid-trinity-ep16`` on one
    16,384-token sequence, differentiated: the one ``q`` leaf with a head's
    query and gate, the q/k norms and the rotation round the splash kernel
    under a 2,048 window (``LocalMask``: 8 query heads a key/value head and
    call, the backward as ``dq`` and ``dkv`` apart), the gate, ``o`` and the
    post-norm."""
    with open(os.path.join(
            REPO, "benchmark/configs/humanoid-trinity-ep16.json")) as f:
        model = json.load(f)["model"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    torso = D4PGConfig(**model).build_critic().torso
    spec = torso.spec
    assert torso.attention_impl() == "splash"
    assert torso.grouped_impl() == "megablox"
    assert spec.rope_for("full_attention") is None
    assert spec.rope_for("sliding_attention")["rope_theta"] == 10000
    params = jax.eval_shape(lambda: torso.init(jax.random.key(0)))
    p = {name: ({"kernel": jax.ShapeDtypeStruct(
        leaf["kernel"].shape, jnp.bfloat16)} if "kernel" in leaf else leaf)
        for name, leaf in params["layer_1"].items()}
    assert p["q"]["kernel"].shape == (2048, 2 * 4096)
    x = jax.ShapeDtypeStruct((spec.tokens, 2048), jnp.float32)
    loss = lambda p, x: jnp.sum(  # noqa: E731
        torso._attend(p, x, "sliding_attention"))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *on(one_chip, (p, x))).compile()
    text = compiled.as_text()
    assert kernel_calls(text, "splash_mqa_fwd") == 1
    assert kernel_calls(text, "splash_mqa_dq") == 1
    assert kernel_calls(text, "splash_mqa_dkv") == 1
    # the largest arrays are the q leaf's [16384, 8192] float32 output (537
    # MB) and what the norms, the rotation and the gate make of its halves
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9
