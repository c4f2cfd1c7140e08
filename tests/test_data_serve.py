"""Data pipelines (determinism, resume, homomorphic accessors) + serving."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import ARCHS, reduced
from repro.data.scientific import ScientificStore, synth_field
from repro.data.tokens import TokenPipeline, TokenPipelineConfig
from repro.models import get_model
from repro.serve import Engine, Request

KEY = jax.random.PRNGKey(0)


# -- token pipeline -----------------------------------------------------------

def test_token_pipeline_deterministic_and_resumable():
    cfg = TokenPipelineConfig(vocab=1000, seq_len=16, global_batch=4)
    a = TokenPipeline(cfg)
    batches = [next(a)["tokens"] for _ in range(5)]
    # resume from step 3 reproduces batches 3,4
    b = TokenPipeline(cfg, start_step=3)
    np.testing.assert_array_equal(next(b)["tokens"], batches[3])
    np.testing.assert_array_equal(next(b)["tokens"], batches[4])
    # state dict roundtrip
    state = a.state_dict()
    c = TokenPipeline(cfg)
    c.load_state_dict(state)
    np.testing.assert_array_equal(next(c)["tokens"], next(a)["tokens"])


def test_token_pipeline_sharding_partitions():
    cfg0 = TokenPipelineConfig(vocab=100, seq_len=8, global_batch=8, n_shards=2, shard=0)
    cfg1 = TokenPipelineConfig(vocab=100, seq_len=8, global_batch=8, n_shards=2, shard=1)
    b0 = TokenPipeline(cfg0).batch_at(0)["tokens"]
    b1 = TokenPipeline(cfg1).batch_at(0)["tokens"]
    assert b0.shape == (4, 8) and b1.shape == (4, 8)
    assert not np.array_equal(b0, b1)  # shards see different data


# -- scientific store ----------------------------------------------------------

def test_scientific_store_homomorphic_stats():
    store = ScientificStore(scale=24, rel_eb=1e-3)
    raw = np.asarray(store.raw("Ocean", 0))
    st = store.stats("Ocean", 0)
    # stage-1/2 stats vs numpy on the DECOMPRESSED field: within paper bounds
    assert abs(st["mean"] - raw.mean()) <= 2e-3 * (raw.max() - raw.min())
    assert abs(st["std"] - raw.std(ddof=1)) <= 2e-3 * (raw.max() - raw.min())


def test_scientific_store_derivative_features():
    store = ScientificStore(scale=24, rel_eb=1e-3)
    g = store.derivative_features("Ocean", 1)
    raw = np.asarray(store.raw("Ocean", 1))
    ref0 = (raw[2:, 1:-1] - raw[:-2, 1:-1]) * 0.5
    np.testing.assert_allclose(np.asarray(g[0]), ref0, atol=1e-5, rtol=1e-4)


def test_scientific_store_disk_roundtrip(tmp_path):
    store = ScientificStore(scale=48, root=str(tmp_path))
    s1 = store.stats("Miranda", 0)
    store2 = ScientificStore(scale=48, root=str(tmp_path))  # reads from disk
    s2 = store2.stats("Miranda", 0)
    assert s1 == s2


def test_synth_field_deterministic():
    a = synth_field("NYX", 2, (16, 16, 16))
    b = synth_field("NYX", 2, (16, 16, 16))
    np.testing.assert_array_equal(a, b)
    # pinned values: the seed must not depend on the process (str hashes
    # are randomized per interpreter), so these hold in every run
    np.testing.assert_allclose(
        [a[0, 0, 0], a[3, 5, 7], a[15, 15, 15]],
        [-0.821628, -0.5784376, -0.19868238], rtol=1e-6)
    o = synth_field("Ocean", 0, (24, 36))
    np.testing.assert_allclose([o[0, 0], o[23, 35]],
                               [-0.12292598, 0.789031], rtol=1e-6)


# -- serving engine --------------------------------------------------------------

@pytest.fixture(scope="module")
def small_model():
    cfg = reduced(ARCHS["smollm-360m"])
    m = get_model(cfg)
    params, _ = m.init(KEY)
    return cfg, m, params


def test_engine_drains_requests(small_model):
    cfg, m, params = small_model
    eng = Engine(m, params, slots=2, max_len=64)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(1, cfg.vocab, 5).astype(np.int32),
                    max_new_tokens=4) for i in range(5)]
    for r in reqs:
        eng.add_request(r)
    done = eng.run_until_drained()
    assert len(done) == 5
    for r in done:
        assert len(r.out_tokens) == 4
        assert all(0 <= t < cfg.vocab for t in r.out_tokens)


def test_engine_greedy_matches_manual_decode(small_model):
    """Single request: engine output == manual greedy decode loop."""
    cfg, m, params = small_model
    prompt = np.asarray([5, 17, 3], np.int32)
    eng = Engine(m, params, slots=1, max_len=32)
    eng.add_request(Request(uid=0, prompt=prompt, max_new_tokens=5))
    out = eng.run_until_drained()[0].out_tokens

    cache = m.init_cache(1, 32)
    toks = list(prompt)
    logits = None
    for t in toks:
        logits, cache = m.decode_step(params, jnp.asarray([[t]], jnp.int32), cache)
    manual = []
    for _ in range(5):
        nxt = int(jnp.argmax(logits[0, -1]))
        manual.append(nxt)
        logits, cache = m.decode_step(params, jnp.asarray([[nxt]], jnp.int32), cache)
    assert out == manual
