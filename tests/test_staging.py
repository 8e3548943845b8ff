"""DeviceStager prefetch semantics (SURVEY.md §7 host<->device overlap):
batches come back in sampling order, host aux (PER indices) rides along
untouched, and invalidate() drops the in-flight batch."""

import numpy as np

from d4pg_tpu.replay.staging import DeviceStager


def test_stager_preserves_order_and_values():
    counter = {"n": 0}

    def sample():
        i = counter["n"]
        counter["n"] += 1
        return np.full((4,), float(i), np.float32)

    st = DeviceStager(sample)
    for expect in range(5):
        got = np.asarray(st.next())
        np.testing.assert_array_equal(got, np.full((4,), float(expect)))
    # one batch is always in flight beyond what was consumed
    assert counter["n"] == 6


def test_stager_aux_rides_on_host():
    counter = {"n": 0}

    def sample():
        i = counter["n"]
        counter["n"] += 1
        payload = {"x": np.full((2,), float(i), np.float32)}
        return payload, ("idx", i)

    st = DeviceStager(sample, with_aux=True)
    p0, aux0 = st.next()
    p1, aux1 = st.next()
    assert aux0 == ("idx", 0) and aux1 == ("idx", 1)
    np.testing.assert_array_equal(np.asarray(p0["x"]), [0.0, 0.0])
    np.testing.assert_array_equal(np.asarray(p1["x"]), [1.0, 1.0])
    # aux stays a host object, payload became a device array
    assert hasattr(p1["x"], "devices")


def test_pipeline_depth_defers_but_never_drops_write_backs():
    """ChunkPipeline keeps up to `depth` chunks in flight: write-backs for
    early chunks are deferred (not yet flushed while the window fills) but
    every chunk's priorities land exactly once by the end of run()."""
    import jax.numpy as jnp

    from d4pg_tpu.learner.pipeline import ChunkPipeline

    n_sampled = {"n": 0}

    def sample():
        i = n_sampled["n"]
        n_sampled["n"] += 1
        return (np.full((2,), float(i), np.float32), None), ("aux", i)

    def update(state, batch, w):
        return state + 1, {"td_error": jnp.full((2,), float(np.asarray(batch)[0]))}

    flushed = []
    pipe = ChunkPipeline(update, sample,
                         write_back=lambda aux, td: flushed.append(
                             (aux[1], float(td[0]))),
                         depth=3)
    state, _ = pipe.run(0, 8)
    assert state == 8
    # every chunk flushed exactly once, in order, with its own td
    assert [f[0] for f in flushed] == list(range(8))
    for i, td in flushed:
        assert np.isclose(td, float(i) + 1e-6)


def test_stager_invalidate_drops_inflight():
    counter = {"n": 0}

    def sample():
        i = counter["n"]
        counter["n"] += 1
        return np.array([float(i)], np.float32)

    st = DeviceStager(sample)
    assert float(np.asarray(st.next())[0]) == 0.0  # 1 staged in flight
    st.invalidate()  # drops sample 1
    assert float(np.asarray(st.next())[0]) == 2.0
