"""The ring as it is stored (PR 31, 33): the rule that pins a wide float
field rows-major and, on a TPU, a rank-4 ``uint8`` field W-minor; the store
that keeps every field in one format for life (``replay/device_ring.py``);
and the input cast that stays behind the gather
(``core/precision.to_compute``). CPU, tiny sizes: the float pin is the
CPU's default, so a *foreign* layout here is column-major; the frames' pin
is not, so a store of frames under the TPU's rule (``frames`` below) is in
the chip's situation as it stands. What the chip's compiler makes of it is
in ``tests/test_torso_v5e_compile.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

from d4pg_tpu.core.precision import to_compute
from d4pg_tpu.io.profiling import RecompileSentinel, abstract_args
from d4pg_tpu.learner import D4PGConfig, init_state
from d4pg_tpu.learner.fused import make_fused_chunk
from d4pg_tpu.learner.loop import FusedLoop
from d4pg_tpu.obs import trace
from d4pg_tpu.replay import device_per as dper
from d4pg_tpu.replay.device_ring import DeviceStore, ring_layout
from d4pg_tpu.replay.fused_buffer import FusedDeviceReplay
from d4pg_tpu.replay.uniform import TransitionBatch

WIDE, ACT, CAP, BLOCK = 128, 3, 64, 16


# ------------------------------------------------------------- the cast ---

def _bits(x):
    return np.asarray(x.view(jnp.uint16))


F32 = np.float32
CASES = {
    "random": np.random.default_rng(0).standard_normal(4096).astype(F32)
    * np.float32(10.0) ** np.random.default_rng(1).integers(-30, 30, 4096),
    # exactly between two bfloat16 neighbours: the even one must win
    "ties": np.array([1.00390625, 1.01171875, -1.00390625, 3.0078125,
                      2.0 ** 100 * 1.00390625], F32),
    "just_off_a_tie": np.nextafter(
        np.array([1.00390625, 1.01171875], F32), F32(2.0)),
    "subnormals": np.array([1e-40, -1e-40, 1e-45, 5.9e-39, 1.1754942e-38],
                           F32),
    "largest": np.array([3.4028235e38, -3.4028235e38, 3.39e38], F32),
    "infinities": np.array([np.inf, -np.inf], F32),
    "nan": np.array([np.nan, -np.nan], F32),
    "zeros": np.array([0.0, -0.0], F32),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_to_compute_gives_the_bits_of_the_plain_cast(case, jitted):
    x = jnp.asarray(CASES[case])
    cast = (lambda v: to_compute(v, jnp.bfloat16))
    got = (jax.jit(cast) if jitted else cast)(x)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(_bits(got), _bits(x.astype(jnp.bfloat16)))


def test_to_compute_is_the_identity_where_nothing_narrows():
    x = jnp.ones((4, 3), jnp.float32)
    assert to_compute(x, jnp.float32) is x
    h = jnp.ones((4,), jnp.bfloat16)
    assert to_compute(h, jnp.bfloat16) is h


def test_to_compute_rounds_only_a_float_narrowed_at_equal_exponent():
    """uint8 frames, widening and float16 (whose subnormals
    ``reduce_precision`` would flush) take the plain cast."""
    text = lambda x, d: str(jax.make_jaxpr(  # noqa: E731
        lambda v: to_compute(v, d))(x))
    f32 = jnp.zeros((2,), jnp.float32)
    assert "reduce_precision" in text(f32, jnp.bfloat16)
    assert "reduce_precision" not in text(f32, jnp.float16)
    assert "reduce_precision" not in text(jnp.zeros((2,), jnp.uint8),
                                          jnp.bfloat16)
    assert "reduce_precision" not in text(jnp.zeros((2,), jnp.bfloat16),
                                          jnp.float32)
    small = jnp.asarray([1e-6, 3e-5, 5.96e-8], jnp.float32)
    np.testing.assert_array_equal(
        _bits(to_compute(small, jnp.float16)),
        _bits(small.astype(jnp.float16)))


def _filled(rng, config, cap):
    trees = dper.set_leaves_jitted(
        dper.init(cap), jnp.arange(cap),
        jnp.asarray(rng.uniform(0.1, 2.0, cap), jnp.float32))
    storage = _rows(rng, cap, obs=config.obs_dim)
    return jax.tree_util.tree_map(jnp.asarray, storage), trees


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_chunk_is_bitwise_the_chunk_of_the_plain_cast(monkeypatch, dtype):
    """K fused steps with ``to_compute`` at the models' inputs against the
    same steps with the ``astype`` it replaced: every leaf of the state and
    both trees equal bit for bit, the backward pass included (the cast of
    the actor's action carries a gradient)."""
    from d4pg_tpu.models import actor, critic

    config = D4PGConfig(obs_dim=24, act_dim=ACT, v_min=-10, v_max=10,
                        n_atoms=11, hidden=(32, 32), compute_dtype=dtype)
    storage, trees = _filled(np.random.default_rng(3), config, CAP)
    state = init_state(config, jax.random.key(7))

    def run():
        fn = make_fused_chunk(config, k=3, batch_size=8, donate=False)
        return fn(state, trees, storage, jnp.int32(CAP))

    new = run()
    plain = lambda x, d: x.astype(d)  # noqa: E731
    monkeypatch.setattr(actor, "to_compute", plain)
    monkeypatch.setattr(critic, "to_compute", plain)
    old = run()
    new, old = (jax.tree_util.tree_map(
        lambda x: np.asarray(jax.random.key_data(x) if jnp.issubdtype(
            x.dtype, jax.dtypes.prng_key) else x), t) for t in (new, old))
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(old)):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------------- the rule ---

FRAMES = (0, 3, 1, 2)  # XLA's {2,1,3,0}: W on the lanes, H on the sublanes


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("shape, dtype, want", [
    ((2101248, 376), np.float32, (0, 1)),   # Humanoid rows: 376 -> 384
    ((33024, 4096), np.float32, (0, 1)),    # the torso cell's histories
    ((80, 128), np.float32, (0, 1)),
    ((80, 114), np.float32, (0, 1)),        # 128 <= 1.125 * 114
    ((80, 113), np.float32, None),
    ((2101248, 17), np.float32, None),      # actions: 7.5x on the lanes
    ((40256, 6), np.float32, None),
    ((2101248,), np.float32, None),         # reward, done, discount
    ((40256, 84, 84, 9), np.uint8, {"tpu": FRAMES}),  # the pixel cell's
    ((576, 20, 20, 3), np.uint8, {"tpu": FRAMES}),    # its rehearsal's
    ((576, 20, 20, 3), np.float32, None),   # rank 4, not frames
    ((576, 20, 20, 3), np.int8, None),
    ((576, 20, 60), np.uint8, None),        # uint8, not rank 4
    ((80, 376), np.uint8, None),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else None)
def test_the_rule_pins_wide_float_rows_and_tpu_frames_and_nothing_else(
        shape, dtype, want, platform):
    """The float pin is the same on every platform (it is the CPU's default
    layout); the frames' pin is the TPU's alone."""
    if isinstance(want, dict):
        want = want.get(platform)
    assert ring_layout(shape, dtype, platform) == want


# ------------------------------------------------------------ the store ---

class Spans:
    def __init__(self):
        self.seen = []

    def __call__(self, name, **stats):
        self.seen.append((name, stats))
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats):
        pass


@pytest.fixture
def spans():
    rec = Spans()
    trace.set_annotator(rec)
    yield rec
    trace.set_annotator(None)


def _rows(rng, n, obs=WIDE):
    """``n`` seeded rows: float observations ``obs`` wide, or ``uint8``
    frames where ``obs`` is a frame's shape."""
    if np.isscalar(obs):
        draw = lambda: rng.standard_normal((n, obs)).astype(np.float32)  # noqa
    else:
        draw = lambda: rng.integers(0, 256, (n, *obs), dtype=np.uint8)  # noqa
    return TransitionBatch(
        obs=draw(),
        action=rng.uniform(-1, 1, (n, ACT)).astype(np.float32),
        reward=rng.standard_normal(n).astype(np.float32),
        next_obs=draw(),
        done=np.zeros(n, np.float32),
        discount=np.full(n, 0.99, np.float32))


FRAME = (20, 20, 3)  # the pixel cell's rehearsal frame


@pytest.fixture(params=["rows", "frames"])
def obs(request, monkeypatch):
    """What a row's observation is: ``WIDE`` floats, pinned rows-major (the
    CPU's default: nothing is foreign until a test makes it so), or a
    ``FRAME`` under the TPU's rule, pinned ``(0, 3, 1, 2)`` (not the CPU's
    default: a new ring is re-laid by its first write, as on the chip)."""
    if request.param == "rows":
        return WIDE
    from d4pg_tpu.replay import device_ring

    rule = device_ring.ring_layout
    monkeypatch.setattr(device_ring, "ring_layout",
                        lambda shape, dtype, platform: rule(shape, dtype,
                                                            "tpu"))
    return FRAME


def _pin(obs):
    return (0, 1) if obs == WIDE else FRAMES


def _store(obs):
    store = DeviceStore(CAP, (WIDE,) if obs == WIDE else obs, ACT,
                        np.float32 if obs == WIDE else np.uint8,
                        block_rows=BLOCK)
    assert store.formats.obs.layout.major_to_minor == _pin(obs)
    return store


def _layouts(storage):
    return [a.format.layout.major_to_minor for a in storage]


def _in_the_stores_formats(store):
    for arr, fmt in zip(store.arrays, store.formats):
        assert arr.committed and arr.sharding == store.home
        if fmt is not None:
            assert arr.format.layout.major_to_minor \
                == fmt.layout.major_to_minor
    return True


def _foreign(values, home):
    """The values as a program that knows nothing of the formats would
    leave them: rank-2 fields column-major, frames in the device's default
    layout, on the same device."""
    col = Format(Layout(major_to_minor=(1, 0)), home)
    return TransitionBatch(*[
        jax.device_put(v, col) if np.ndim(v) == 2 else jnp.asarray(v)
        for v in values])


def test_the_store_pins_obs_and_next_obs_and_leaves_the_rest():
    store = DeviceStore(CAP, (WIDE,), ACT, np.float32, block_rows=BLOCK)
    pinned = [f is not None for f in store.formats]
    assert pinned == [True, False, False, True, False, False]
    assert _in_the_stores_formats(store)
    narrow = DeviceStore(CAP, (5,), ACT, np.float32)
    assert not any(f is not None for f in narrow.formats)
    frames = DeviceStore(8, (12, 12, 9), ACT, np.uint8)
    assert not any(f is not None for f in frames.formats)


@pytest.mark.parametrize("path", ["write", "write_block", "swap_foreign"])
def test_every_path_leaves_the_ring_in_the_stores_formats(rng, spans, path,
                                                          obs):
    store = _store(obs)
    vals = _rows(rng, CAP + BLOCK, obs)
    if path == "write":
        store.write(np.arange(5, dtype=np.int32),
                    TransitionBatch(*[v[:5] for v in vals]))
        want = np.zeros_like(vals.obs)
        want[:5] = vals.obs[:5]
    elif path == "write_block":
        store.write_block(60, TransitionBatch(*[v[:BLOCK] for v in vals]), 9)
        want = np.zeros_like(vals.obs)
        want[60:69] = vals.obs[:9]
        want[:5] = vals.obs[4:9]  # the wrapped spill, mirrored to the head
    else:
        store.swap_arrays(_foreign(vals, store.home))
        want = vals.obs
    assert _in_the_stores_formats(store)
    np.testing.assert_array_equal(np.asarray(store.arrays.obs), want)
    relaid = [s for s in spans.seen if s[0] == "ring.relayout"]
    # a pin that is the device's default re-lays only what came in foreign;
    # one that is not re-lays a new ring at its first write too
    assert len(relaid) == (2 if path == "swap_foreign" or obs != WIDE else 0)


def test_swap_arrays_relays_a_foreign_field_once_and_only_then(rng, spans,
                                                               obs):
    """The one door a foreign layout comes through: each pinned field that
    arrives in another layout is re-laid under one ``ring.relayout`` span
    naming it and its bytes, its source donated; a second swap of what the
    store now holds, and a swap of arrays already in its formats, open
    none."""
    store = _store(obs)
    vals = _rows(rng, CAP + BLOCK, obs)
    foreign = _foreign(vals, store.home)
    assert _layouts(foreign)[0] == ((1, 0) if obs == WIDE else (0, 1, 2, 3))
    store.swap_arrays(foreign)
    relaid = [s for s in spans.seen if s[0] == "ring.relayout"]
    assert [s[1]["field"] for s in relaid] == ["obs", "next_obs"]
    assert all(s[1]["bytes"] == vals.obs.nbytes for s in relaid)
    assert foreign.obs.is_deleted() and foreign.next_obs.is_deleted()
    assert not foreign.action.is_deleted()  # unpinned: the same buffer
    for got, want in zip(store.arrays, vals):
        np.testing.assert_array_equal(np.asarray(got), want)
    spans.seen.clear()
    held = store.arrays
    store.swap_arrays(held)
    assert all(a is b for a, b in zip(store.arrays, held))
    assert not [s for s in spans.seen if s[0] == "ring.relayout"]


def test_a_fused_commit_returns_the_ring_in_its_formats_and_in_place(
        rng, spans, obs):
    buf = FusedDeviceReplay(CAP, obs, ACT, alpha=0.6, block_rows=BLOCK)
    assert buf.home == buf._store.home
    assert all(t.committed for t in jax.tree_util.tree_leaves(buf.trees))
    # (a pin that is not the device's default re-lays a never-written ring
    # at its first commit: the last test of this file; here, the ring after)
    buf._store.pinned()
    spans.seen.clear()
    buf.add(_rows(rng, 40, obs))
    before = buf.storage.obs.unsafe_buffer_pointer()
    assert buf.drain() == 40
    assert _in_the_stores_formats(buf._store)
    assert _layouts(buf.storage)[0] == _pin(obs)
    # donated in, the same format out: the commit updated the ring in place
    assert buf.storage.obs.unsafe_buffer_pointer() == before
    assert not [s for s in spans.seen if s[0] == "ring.relayout"]
    # a restore (the scatter write) keeps them too
    other = FusedDeviceReplay(CAP, obs, ACT, alpha=0.6, block_rows=BLOCK)
    other.load_state_dict(buf.state_dict())
    assert _in_the_stores_formats(other._store)
    np.testing.assert_array_equal(np.asarray(other.storage.obs[:40]),
                                  np.asarray(buf.storage.obs[:40]))
    assert all(t.committed for t in jax.tree_util.tree_leaves(other.trees))


def test_the_loop_compiles_one_chunk_program_for_a_foreign_filled_ring(rng,
                                                                       obs):
    """The benchmark's fill and a restored checkpoint hand the store a ring
    it did not lay out; the loop then commits the state it is given to the
    ring's device, so the second chunk is the first chunk's program (what a
    program returns is committed once an argument is) and the program
    table's abstract arguments carry the ring's formats."""
    model = dict(obs_dim=WIDE) if obs == WIDE else dict(
        obs_dim=0, pixels=True, obs_shape=FRAME, encoder_channels=(4, 4),
        augment="shift", augment_pad=2)
    config = D4PGConfig(act_dim=ACT, v_min=-10, v_max=10, n_atoms=11,
                        hidden=(16, 16), **model)
    buf = FusedDeviceReplay(CAP, obs, ACT, alpha=0.6, block_rows=BLOCK)
    vals = _rows(rng, CAP + BLOCK, obs)
    buf._store.swap_arrays(_foreign(vals, buf.home))
    buf.size, buf.head = CAP, 0
    buf.trees = dper.set_leaves_jitted(
        buf.trees, jnp.arange(CAP), jnp.ones(CAP, jnp.float32))
    loop = FusedLoop(config, buf, k=2, batch_size=8)
    state = init_state(config, jax.random.key(0))
    assert not state.step.committed
    state, _m = loop.run(state, 2)
    with RecompileSentinel() as sentinel:
        state, m = loop.run(state, 4)
        jax.block_until_ready(state)
    sentinel.assert_clean("the second and third chunk")
    _fn, args = trace._PROGRAMS["learner.chunk"]
    ring = args[2]
    assert ring.obs.format.layout.major_to_minor == _pin(obs)
    assert ring.obs.sharding == buf.home
    plain = abstract_args((jnp.zeros((3, 3)),))[0]
    assert plain.sharding is None
    # and the rows the chunk drew read back as they were filled
    idx = np.asarray(m["idx"][-1])
    np.testing.assert_array_equal(np.asarray(buf._store.read(idx).obs),
                                  vals.obs[idx])


# ------------------------------------------- outside the persistent cache ---

def test_fresh_compile_neither_reads_nor_writes_the_persistent_cache(
        tmp_path):
    """An executable read back from the persistent cache reports the default
    layout for what it returns (the chip, PR 31), so the programs that
    return a pinned field compile outside it; everything else keeps it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from d4pg_tpu.io.profiling import fresh_compile

    placed = {"jax_compilation_cache_dir": str(tmp_path),
              "jax_persistent_cache_min_compile_time_secs": 0.0,
              "jax_persistent_cache_min_entry_size_bytes": -1}
    before = {flag: getattr(jax.config, flag) for flag in placed}
    for flag, value in placed.items():
        jax.config.update(flag, value)
    cc.reset_cache()
    try:
        entries = lambda: sorted(  # noqa: E731
            p.name for p in tmp_path.iterdir()
            if "lambda" in p.name and p.name.endswith("-cache"))
        x = jnp.ones(7)
        with fresh_compile():
            assert not jax.config.jax_enable_compilation_cache
            jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
        assert jax.config.jax_enable_compilation_cache
        assert entries() == []
        jax.jit(lambda x: x * 5 + 2)(x).block_until_ready()
        assert len(entries()) == 1
        with RecompileSentinel() as compiles, fresh_compile():
            # the same program again, a new jit object: it would be read
            # back from the cache; inside, it is compiled
            jax.jit(lambda x: x * 5 + 2)(x).block_until_ready()
        assert compiles.compilations == 1 and len(entries()) == 1
    finally:
        for flag, value in before.items():
            jax.config.update(flag, value)
        cc.reset_cache()


def test_a_fresh_program_compiles_once_a_signature_and_survives_clear_cache():
    from d4pg_tpu.io.profiling import FreshProgram

    fn = jax.jit(lambda a, n: a.at[n].add(1.0), donate_argnums=(0,))
    prog = FreshProgram(fn)
    a = jnp.zeros((8, 4))
    with RecompileSentinel() as first:
        a = prog(a, np.int32(2))
    with RecompileSentinel() as later:
        a = prog(a, np.int32(3))
        fn.clear_cache()  # what a trace reader does to the table's entry
        a = prog(a, np.int32(2))
    assert first.compilations == 1 and later.compilations == 0
    np.testing.assert_array_equal(np.asarray(a)[:, 0],
                                  [0, 0, 2, 1, 0, 0, 0, 0])
    wider = jnp.zeros((16, 4))
    with RecompileSentinel() as other:
        b = prog(wider, np.int32(1))  # another signature
    assert other.compilations == 1 and b.shape == (16, 4)


def test_only_a_store_with_a_pinned_field_goes_round_the_cache():
    from d4pg_tpu.io.profiling import FreshProgram

    wide = FusedDeviceReplay(CAP, WIDE, ACT, block_rows=BLOCK)
    narrow = FusedDeviceReplay(CAP, 5, ACT, block_rows=BLOCK)
    frames = FusedDeviceReplay(CAP, FRAME, ACT, block_rows=BLOCK)
    assert isinstance(wide._commit, FreshProgram)
    assert isinstance(wide._store._insert, FreshProgram)
    assert isinstance(wide._store._write_block, FreshProgram)
    assert wide._commit.fn is wide._commit_fn
    # (frames are pinned on a TPU alone: here the store's device is a CPU)
    for buf in (narrow, frames):
        for prog in (buf._commit, buf._store._insert,
                     buf._store._write_block):
            assert not isinstance(prog, FreshProgram)


def test_a_pixel_store_on_the_cpu_is_never_relaid(rng, spans):
    """``(0, 3, 1, 2)`` is not the CPU's default layout: pinned here, every
    pixel store of the suite would transpose its ring. The rule reads the
    platform of the store's device, so nothing is pinned, every path keeps
    the default layout and no ``ring.relayout`` span opens."""
    buf = FusedDeviceReplay(CAP, FRAME, ACT, alpha=0.6, block_rows=BLOCK)
    store = buf._store
    assert next(iter(store.home.device_set)).platform == "cpu"
    assert all(f is None for f in store.formats)
    vals = _rows(rng, CAP + BLOCK, FRAME)
    store.swap_arrays(_foreign(vals, store.home))
    buf.size, buf.head = CAP, 0
    more = _rows(rng, 2 * BLOCK, FRAME)
    buf.add(TransitionBatch(*[v[:BLOCK] for v in more]))
    assert buf.drain() == BLOCK
    store.write_block(BLOCK, TransitionBatch(*[v[BLOCK:] for v in more]),
                      BLOCK)
    store.write(np.arange(40, 44, dtype=np.int32),
                TransitionBatch(*[v[:4] for v in more]))
    assert _layouts(store.arrays)[0] == _layouts(store.arrays)[3] \
        == (0, 1, 2, 3)
    assert not [s for s in spans.seen if s[0] == "ring.relayout"]
    want = vals.obs.copy()
    want[:2 * BLOCK] = more.obs
    want[40:44] = more.obs[:4]
    np.testing.assert_array_equal(np.asarray(store.arrays.obs), want)


# ------------------------------------ the chip's situation, on the CPU ---

@pytest.fixture
def pinned_is_not_the_default(monkeypatch):
    """On the chip the pinned layout differs from the allocator's; here they
    are one. Pinning COLUMN-major instead puts the CPU in the chip's place:
    a new ring that has to be re-laid, programs that must keep it so."""
    from d4pg_tpu.replay import device_ring

    monkeypatch.setattr(
        device_ring, "ring_layout",
        lambda shape, dtype, platform: (1, 0)
        if len(shape) == 2 and shape[1] >= WIDE else None)


def test_a_new_ring_is_the_allocators_until_the_first_write(
        rng, spans, pinned_is_not_the_default):
    """A program that knows nothing of the formats (the benchmark's seeded
    fill) takes a new store's ring donated and fills it in place: no room
    for a second ring there. The first ``swap_arrays`` (or write of the
    store's own) then re-lays each pinned field once, and every program
    after keeps it: in place, no second re-layout."""
    buf = FusedDeviceReplay(CAP, WIDE, ACT, alpha=0.6, block_rows=BLOCK)
    store = buf._store
    assert _layouts(store.arrays)[0] == (0, 1) and not store._pinned
    vals = _rows(rng, CAP + BLOCK)
    fill = jax.jit(lambda storage, new: TransitionBatch(*[
        jax.lax.dynamic_update_slice_in_dim(a, v, 0, 0)
        for a, v in zip(storage, new)]), donate_argnums=(0,))
    before = store.arrays.obs.unsafe_buffer_pointer()
    filled = fill(store.arrays, vals)
    assert filled.obs.unsafe_buffer_pointer() == before  # in place
    assert _layouts(filled)[0] == (0, 1)
    store.swap_arrays(filled)
    buf.size, buf.head = CAP, 0
    assert [s[1]["field"] for s in spans.seen
            if s[0] == "ring.relayout"] == ["obs", "next_obs"]
    assert _layouts(store.arrays) == [(1, 0), (0, 1), (0,), (1, 0), (0,),
                                      (0,)]
    spans.seen.clear()
    # the block commit, the block write and the scatter write: pinned in,
    # pinned out, the same buffer
    more = _rows(rng, 2 * BLOCK)
    before = store.arrays.obs.unsafe_buffer_pointer()
    buf.add(TransitionBatch(*[v[:BLOCK] for v in more]))
    assert buf.drain() == BLOCK
    store.write_block(BLOCK, TransitionBatch(*[v[BLOCK:] for v in more]),
                      BLOCK)
    store.write(np.arange(40, 44, dtype=np.int32),
                TransitionBatch(*[v[:4] for v in more]))
    assert store.arrays.obs.unsafe_buffer_pointer() == before
    assert _layouts(store.arrays)[0] == (1, 0)
    assert not [s for s in spans.seen if s[0] == "ring.relayout"]
    want = vals.obs.copy()
    want[:2 * BLOCK] = more.obs
    want[40:44] = more.obs[:4]
    np.testing.assert_array_equal(np.asarray(store.arrays.obs), want)
    # and the chunk takes it as it is stored
    config = D4PGConfig(obs_dim=WIDE, act_dim=ACT, v_min=-10, v_max=10,
                        n_atoms=11, hidden=(16, 16))
    buf.trees = dper.set_leaves_jitted(
        buf.trees, jnp.arange(CAP), jnp.ones(CAP, jnp.float32))
    loop = FusedLoop(config, buf, k=2, batch_size=8)
    state, m = loop.run(init_state(config, jax.random.key(0)), 4)
    assert np.isfinite(np.asarray(m["critic_loss"])).all()
    text = trace.compiled_text("learner.chunk")
    assert "f32[%d,%d]{0,1} parameter(" % (CAP + BLOCK, WIDE) in text
    got = store.read(np.asarray(m["idx"][-1]))
    np.testing.assert_array_equal(np.asarray(got.obs),
                                  want[np.asarray(m["idx"][-1])])


def test_a_never_filled_ring_is_pinned_by_the_first_commit(
        rng, spans, pinned_is_not_the_default):
    """``train`` never fills from outside: its first block commit finds the
    allocator's (zero) ring and re-lays it first, once."""
    buf = FusedDeviceReplay(CAP, WIDE, ACT, alpha=0.6, block_rows=BLOCK)
    for _ in range(3):
        buf.add(_rows(rng, BLOCK))
        assert buf.drain() == BLOCK
    assert [s[1]["field"] for s in spans.seen
            if s[0] == "ring.relayout"] == ["obs", "next_obs"]
    assert _layouts(buf.storage)[0] == (1, 0) and buf._store._pinned
