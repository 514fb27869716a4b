"""Content-addressed store (IPFS analogue) behaviour."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.store import (StoreNetwork, StoreNode, compute_cid,
                              deserialize_pytree, serialize_pytree)


def _tree():
    return {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": np.ones((4,), np.float32)}


def test_serialize_roundtrip():
    t = _tree()
    data = serialize_pytree(t)
    back = deserialize_pytree(data, like=t)
    for k in t:
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(t[k]))


def test_cid_deterministic_and_content_addressed():
    t = _tree()
    d1, d2 = serialize_pytree(t), serialize_pytree(t)
    assert compute_cid(d1) == compute_cid(d2)
    t2 = _tree()
    t2["w"] = t2["w"] + 1
    assert compute_cid(serialize_pytree(t2)) != compute_cid(d1)


def test_put_get_local():
    node = StoreNode("n0")
    cid = node.put(_tree())
    got = node.get(cid, like=_tree())
    np.testing.assert_array_equal(np.asarray(got["w"]), _tree()["w"])


def test_peer_fetch_and_cache():
    net = StoreNetwork()
    a = net.add_node("a")
    b = net.add_node("b")
    cid = a.put(_tree())
    assert not b.has(cid)
    got = b.get(cid, like=_tree())  # DHT-ish fetch from a
    np.testing.assert_array_equal(np.asarray(got["w"]), _tree()["w"])
    assert b.has(cid)  # cached locally now
    assert b.stats["peer_fetches"] == 1


def test_missing_cid_raises():
    node = StoreNode("solo")
    with pytest.raises(KeyError):
        node.get_bytes("bafy" + "0" * 64)


def test_node_failure_other_replicas_survive():
    net = StoreNetwork()
    a, b, c = net.add_node("a"), net.add_node("b"), net.add_node("c")
    cid = a.put(_tree())
    b.get(cid)            # b now caches a replica
    net.drop_node("a")    # a dies
    got = c.get(cid)      # c fetches from b
    assert got is not None


def test_gc_respects_pins():
    node = StoreNode("n")
    cid_pinned = node.put(_tree(), pin=True)
    cid_loose = node.put({"x": np.zeros(3)}, pin=False)
    node.gc()
    assert node.has(cid_pinned)
    assert not node.has(cid_loose)


def test_integrity_verified_on_peer_fetch():
    net = StoreNetwork()
    a, b = net.add_node("a"), net.add_node("b")
    cid = a.put(_tree())
    # corrupt a's block
    a._blocks[cid] = b"corrupted"
    with pytest.raises((IOError, KeyError)):
        b.get_bytes(cid)


@pytest.mark.parametrize("on_disk", [False, True])
def test_retain_bounds_memory_and_keeps_delta_bases(tmp_path, on_disk):
    """``retain`` keeps the named CIDs and, with ``with_bases``, the delta
    chain under them; it drops the rest from memory (pins and decoded
    models too), while files under ``root`` stay readable."""
    from repro.config import FedConfig
    from repro.core import wire

    root = str(tmp_path) if on_disk else None
    net = StoreNetwork()
    a = net.add_node("a", root)
    fed = FedConfig(compression="int8-delta")
    vec = np.linspace(-1, 1, 4096).astype(np.float32)
    env0 = wire.encode_update({"w": vec}, fed)
    base = a.put(env0.to_store())
    dm = a.get_decoded(base, a.wire_decoder())
    env1 = wire.encode_update({"w": vec * 1.5}, fed, base=(base, dm.vec()))
    top = a.put(env1.to_store())
    stale = a.put(_tree())
    assert a.retain({top}, with_bases=True) == [stale]
    assert a.held_bytes() == sum(len(a.read_local(c)) for c in (base, top))
    a.get_decoded(top, a.wire_decoder()).vec()
    assert a.has_decoded(base)
    dropped = set(a.retain({top}))
    assert dropped == {base}
    assert not a.has_decoded(base) and base not in a._pins
    assert bool(a.has(base)) == on_disk


def _checkpoint_tree():
    rng = np.random.default_rng(0)
    return {"params": {
        "embed": jnp.asarray(rng.normal(size=(512, 1024)), jnp.bfloat16),
        "mlp": jnp.asarray(rng.normal(size=(64, 96)), jnp.bfloat16),
        "norm": jnp.asarray(rng.normal(size=(1024,)), jnp.float32)},
        "round": np.asarray(7)}


def _int8_tree():
    rng = np.random.default_rng(1)
    return {"q": jnp.asarray(rng.integers(-127, 128, (1024, 1030)), jnp.int8),
            "scales": jnp.asarray(rng.random(1030), jnp.float32)}


def _odd_tree():
    rng = np.random.default_rng(2)
    return {"w": jnp.asarray(rng.normal(size=(1 << 19) + 3), jnp.bfloat16),
            "b": jnp.asarray(rng.integers(-9, 9, 5), jnp.int8),
            "flags": jnp.asarray([True, False, True])}


@pytest.mark.parametrize("make", [_checkpoint_tree, _int8_tree, _odd_tree],
                         ids=["checkpoint", "int8", "odd-length"])
def test_a_device_payload_is_packed_into_the_host_image(make):
    """A payload whose leaves live on the device is built on the device and
    crosses in one transfer: the same bytes and CID as the host path, held
    read-only and viewed by the decoded leaves, one packed put, and one
    compile per pytree structure whatever the values."""
    import jax
    from repro.core import store
    from repro.obs import wall

    tree = make()
    host = jax.tree.map(np.asarray, tree)
    want = serialize_pytree(host)
    assert isinstance(want, bytearray)       # host leaves: the host path
    node = StoreNode("n")
    wall.switch(True)
    try:
        cid = node.put(tree)
        t = wall.totals()
    finally:
        wall.switch(False)
    block = node.read_local(cid)
    assert isinstance(block, np.ndarray) and block.dtype == np.uint8
    assert bytes(block) == bytes(want)
    assert cid == compute_cid(want)
    assert not block.flags.writeable and block.flags.c_contiguous
    flat = deserialize_pytree(block)
    for got, leaf in zip(flat.values(), jax.tree_util.tree_leaves(host)):
        assert np.shares_memory(got, block)
        np.testing.assert_array_equal(got, leaf)
    # one put packed, one transfer of the image's 32-bit words
    assert t["packed_puts"] == 1 and t["host_syncs"] == 1
    assert t["d2h_bytes"] == -(-len(block) // 4) * 4
    # two checkpoints that differ in a host value only: one compile
    n0 = store._pack._cache_size()
    renamed = {"fresh": tree}
    a = serialize_pytree({**renamed, "round": np.asarray(1)})
    b = serialize_pytree({**renamed, "round": np.asarray(2)})
    assert store._pack._cache_size() == n0 + 1
    assert bytes(a[:-8]) == bytes(b[:-8]) and bytes(a) != bytes(b)
