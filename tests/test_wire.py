"""repro.core.wire: the one model-exchange codec.

Envelope round-trips through ``store.put``/``get_decoded`` must be
bit-exact for every wire method x delta/no-delta combination (quantization
happens at encode; the store/serialization layers may not perturb a single
bit), each method keeps its fidelity and size, delta envelopes must resolve
their base chain through the store's decoded cache, and a round's int8
payloads stack for the device in one place (``stack_q8``).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import wire
from repro.core.store import StoreNetwork, StoreNode
from repro.kernels import ops
from repro.obs import wall

try:  # property tests run under hypothesis when available (CI installs it);
    # otherwise a fixed seed/length sweep keeps the same invariant covered
    from hypothesis import given, settings, strategies as st
except ImportError:
    st = None

METHOD_COMBOS = [("raw", False), ("int8", False),
                 ("int8-delta", False), ("int8-delta", True),
                 ("topk-delta", False), ("topk-delta", True)]


def _vec(seed: int, n: int, scale: float = 1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(n) * scale, jnp.float32)


def _put_base(node: StoreNode, base_vec) -> str:
    return node.put(wire.encode_vec(base_vec, "int8").to_store())


def _encode_with_optional_base(node, vec, method, with_base, seed):
    """(envelope, decoded-base-vec or None). The base is itself a stored
    int8 envelope; deltas are computed against its *decoded* form, exactly
    like the round path does."""
    if not with_base:
        return wire.encode_vec(vec, method), None
    base_vec = vec + _vec(seed + 1, int(vec.shape[0]), 0.05)
    base_cid = _put_base(node, base_vec)
    base_dec = node.get_decoded(base_cid, node.wire_decoder()).vec()
    env = wire.encode_vec(vec, method, base_vec=base_dec, base_cid=base_cid)
    return env, base_dec


@pytest.mark.parametrize("method,with_base", METHOD_COMBOS)
def test_roundtrip_through_store_bit_exact(method, with_base):
    node = StoreNode("n0")
    n = 5000
    vec = _vec(7, n)
    env, base_dec = _encode_with_optional_base(node, vec, method, with_base, 7)
    cid = node.put(env.to_store())
    dm = node.get_decoded(cid, node.wire_decoder())
    assert dm.n == n
    assert dm.method == wire.resolve_method(method) or method == "int8-delta"
    assert dm.base_cid == env.base_cid
    # payload arrays survive serialization bit-exactly
    for f in ("q", "scales", "tiles", "idx", "vals"):
        a = getattr(env, f)
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(getattr(dm, f)))
    # ... and so does the reconstruction (same fused path both sides)
    want = env.reconstruct(base_dec)
    np.testing.assert_array_equal(np.asarray(dm.vec()), np.asarray(want))


@pytest.mark.parametrize("method,with_base", METHOD_COMBOS)
def test_reconstruct_fused_matches_ref_path(method, with_base):
    """Bit-parity budget of the fused reconstruction vs the unfused oracle:
    within the existing q8 kernel tolerance."""
    node = StoreNode("n0")
    vec = _vec(11, 4000)
    env, base_dec = _encode_with_optional_base(node, vec, method, with_base,
                                               11)
    fused = env.reconstruct(base_dec)
    ref = env.reconstruct(base_dec, force="ref")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def _roundtrip_any_length(seed, n, combo):
    method, with_base = combo
    node = StoreNode("p0")
    vec = _vec(seed, n)
    env, base_dec = _encode_with_optional_base(node, vec, method, with_base,
                                               seed)
    cid = node.put(env.to_store())
    dm = node.get_decoded(cid, node.wire_decoder())
    assert dm.n == n
    got = np.asarray(dm.vec())
    assert got.shape == (n,)
    np.testing.assert_array_equal(got, np.asarray(env.reconstruct(base_dec)))
    if method == "raw":  # lossless method: exact payload round-trip
        np.testing.assert_array_equal(got, np.asarray(vec))


if st is not None:
    @settings(max_examples=12, deadline=None)
    @given(seed=st.integers(0, 2**20), n=st.integers(1, 9000),
           combo=st.sampled_from(METHOD_COMBOS))
    def test_property_roundtrip_any_length(seed, n, combo):
        _roundtrip_any_length(seed, n, combo)
else:
    @pytest.mark.parametrize("seed,n", [(0, 1), (1, 17), (2, 1024),
                                        (3, 1025), (4, 8191)])
    @pytest.mark.parametrize("combo", METHOD_COMBOS)
    def test_property_roundtrip_any_length(combo, seed, n):
        _roundtrip_any_length(seed, n, combo)


# --------------------------------------------------------------------------- #
# Fidelity and size of each method
# --------------------------------------------------------------------------- #

def test_int8_roundtrip_error_bound():
    vec = jnp.concatenate([_vec(0, 5000), _vec(1, 64)])
    back = wire.encode_vec(vec, "int8").reconstruct()
    # quantization tiles span leaf boundaries: the bound is the GLOBAL amax
    amax = float(jnp.max(jnp.abs(vec)))
    err = float(jnp.max(jnp.abs(back - vec)))
    assert err <= amax / 127.0 * 0.51 + 1e-5


def test_int8_compresses_4x():
    vec = _vec(0, 200_064)
    env = wire.encode_vec(vec, "int8")
    assert env.nbytes() < vec.nbytes / 3.0  # ~4x minus scale overhead


def test_topk_delta_keeps_largest():
    base = _vec(1, 5064)
    vec = base.at[7].add(100.0)  # one big delta
    env = wire.encode_vec(vec, "topk-delta", base_vec=base, base_cid="b",
                          topk_frac=0.001)
    back = env.reconstruct(base)
    assert abs(float(back[7] - vec[7])) < 1e-3
    # untouched coordinates come back as base
    np.testing.assert_allclose(np.asarray(back[5000:]),
                               np.asarray(base[5000:]), atol=1e-5)


def test_none_passthrough():
    vec = _vec(0, 5064)
    env = wire.encode_vec(vec, "none")
    assert env.method == "raw"
    np.testing.assert_array_equal(np.asarray(env.reconstruct()),
                                  np.asarray(vec))


# --------------------------------------------------------------------------- #
# Stacking a round's payloads for the device
# --------------------------------------------------------------------------- #

def test_stack_q8_stacks_payloads_and_counts_their_bytes_once():
    node = StoreNode("n0")
    decoded = [node.get_decoded(node.put(wire.encode_vec(
        _vec(i, 7000), "int8").to_store()), node.wire_decoder())
        for i in range(3)]
    wall.switch(True)
    try:
        before = wall.totals()
        q, s = wire.stack_q8(decoded)
        d = wall.diff(before, wall.totals())
    finally:
        wall.switch(False)
    npad = 7000 + (-7000) % ops.QUANT_BLOCK
    assert q.shape == (3, npad) and q.dtype == np.int8
    assert s.shape == (3, npad // wire.QT) and s.dtype == np.float32
    for i, dm in enumerate(decoded):
        np.testing.assert_array_equal(q[i], dm.q)
        np.testing.assert_array_equal(s[i], dm.scales)
    assert d["h2d_bytes"] == q.nbytes + s.nbytes


def test_stack_q8_rejects_two_padded_lengths():
    node = StoreNode("n0")
    short, long_ = (node.get_decoded(node.put(wire.encode_vec(
        _vec(i, n), "int8").to_store()), node.wire_decoder())
        for i, n in enumerate((100, ops.QUANT_BLOCK + 1)))
    with pytest.raises(ValueError,
                       match=f"{ops.QUANT_BLOCK}.*{2 * ops.QUANT_BLOCK}"):
        wire.stack_q8([short, long_])


def test_delta_base_chain_resolves_across_peers():
    """A delta envelope pulled by a peer that never saw the base fetches the
    base CID through the store network and reconstructs correctly."""
    net = StoreNetwork()
    a, b = net.add_node("a"), net.add_node("b")
    base_vec = _vec(1, 6000)
    vec = base_vec + _vec(2, 6000, 0.1)
    base_cid = _put_base(a, base_vec)
    base_dec = a.get_decoded(base_cid, a.wire_decoder()).vec()
    env = wire.encode_vec(vec, "int8-delta", base_vec=base_dec,
                          base_cid=base_cid)
    assert env.method == "int8-delta" and env.nbytes() < 131072 // 2
    cid = a.put(env.to_store())
    dm = b.get_decoded(cid, b.wire_decoder())     # b has neither CID locally
    got = dm.vec()                                 # resolves base via peer a
    assert b.has(base_cid)                         # chain was fetched
    np.testing.assert_allclose(np.asarray(got), np.asarray(vec), atol=0.05)
    # decoded cache keys on (cid, resolved_base)
    assert (cid, base_cid) in b._decoded
    assert (base_cid, "") in b._decoded


def test_delta_chain_of_chains():
    """Round r's envelope deltas against round r-1's, recursively; vec()
    walks the whole chain through the decoded cache."""
    node = StoreNode("n0")
    n = 5000
    vecs = [_vec(10, n)]
    cids = [_put_base(node, vecs[0])]
    for r in range(1, 4):
        vecs.append(vecs[-1] + _vec(10 + r, n, 0.05))
        base_dec = node.get_decoded(cids[-1], node.wire_decoder()).vec()
        env = wire.encode_vec(vecs[-1], "int8-delta", base_vec=base_dec,
                              base_cid=cids[-1])
        cids.append(node.put(env.to_store()))
    dm = node.get_decoded(cids[-1], node.wire_decoder())
    np.testing.assert_allclose(np.asarray(dm.vec()), np.asarray(vecs[-1]),
                               atol=0.1)


def test_noise_floor_elision_drops_quiet_tiles():
    """Tiles whose delta stays under the base's quantization step are elided
    (they are unrepresentable at q8 wire fidelity anyway)."""
    n = 8 * wire.QT
    base = _vec(5, n)
    vec = jnp.asarray(np.asarray(base))
    # perturb exactly one tile well above the noise floor
    vec = vec.at[3 * wire.QT + 17].add(1.0)
    env = wire.encode_vec(vec, "int8-delta", base_vec=base, base_cid="b")
    assert np.asarray(env.tiles).tolist() == [3]
    got = env.reconstruct(base)
    np.testing.assert_allclose(np.asarray(got), np.asarray(vec), atol=0.01)


def test_unknown_wire_version_rejected():
    node = StoreNode("n0")
    payload = wire.encode_vec(_vec(0, 100), "int8").to_store()
    payload["__wire__"] = np.asarray(wire.WIRE_VERSION + 1, np.int64)
    cid = node.put(payload)
    with pytest.raises(ValueError, match="newer"):
        node.get_decoded(cid, node.wire_decoder())


def test_keyframe_bounds_delta_chain_walk():
    """Long-chain compaction: with ``FedConfig.keyframe_every = k`` every
    k-th announced envelope ships whole (int8 keyframe), so a late joiner /
    post-reorg catch-up never walks more than k-1 delta links."""
    from repro.config import FedConfig
    from repro.configs import get_config
    from repro.core.builder import build_image_experiment

    fed = FedConfig(n_silos=2, clients_per_silo=1, rounds=4, local_epochs=1,
                    mode="sync", scorer="accuracy", agg_policy="all",
                    score_policy="median", compression="int8-delta",
                    keyframe_every=2)
    orch = build_image_experiment(get_config("paper-cnn"), fed, n_train=200,
                                  n_test=80, seed=0)
    orch.run(4)
    depths = []
    for s in orch.silos:
        assert s._announces == 4
        for cid in list(s.store._blocks):
            depths.append(wire.chain_depth_of(s.store, cid))
    assert max(depths) <= fed.keyframe_every - 1     # walk bound holds
    assert any(d == 1 for d in depths)               # and deltas do exist


def test_grep_gate_method_key_only_in_wire():
    """Acceptance: the '__method__' envelope key appears in exactly one
    module under src/ — repro/core/wire.py, the codec."""
    import pathlib
    root = pathlib.Path(__file__).resolve().parent.parent / "src"
    offenders = [p for p in root.rglob("*.py")
                 if "__method__" in p.read_text()
                 and p.name != "wire.py"]
    assert offenders == [], f"__method__ leaked outside wire.py: {offenders}"
