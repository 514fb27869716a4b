"""Content-addressed store — the IPFS analogue (paper §2.4, §3.4.2).

Properties kept from IPFS: content addressing (CID = SHA-256 of canonical
bytes, by 1 MiB blocks past one block), integrity verification on fetch,
immutability, per-node local blocks with peer fetch-and-cache (DHT-like),
pinning, and hosting store nodes on the aggregator machines themselves.
Serialization is a deterministic pytree codec (JSON header + raw array
bytes). A node holds each CID's bytes as one buffer, never written once it
lands, which the decoded models it hands out view without a copy.

A ``StoreNetwork`` connects per-silo ``StoreNode``s; ``get`` falls back to
peers and caches locally (exactly the IPFS behaviour the paper relies on for
"scorers pull model weights").

With a ``repro.net.NetFabric`` attached, peer fetches stop being free: the
provider is chosen DHT-style from the fabric's records (nearest reachable
replica, not always the origin), the transfer is charged simulated time on
the (src, dst) link, and per-node accounting lands in ``stats``
(``bytes_in`` / ``bytes_out`` / ``fetch_time`` / ``replica_hits`` /
``prefetch_hits``). ``drain_transfer_time`` hands the accumulated charge to
the orchestrator so WAN time enters the simulated clock.

Memory is bounded by ``retain``: the caller names the CIDs the protocol can
still read, and the node drops the rest from memory (blocks, pins, decoded
models and, where nothing on disk serves them, its provider records). The
wall counters ``store_bytes_in`` and ``store_bytes_dropped`` count every
buffer a node takes in and lets go, so their difference is what all nodes
hold.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.obs import wall
from repro.obs.metrics import StatsView

DECODED_CACHE_MAX = 64  # CIDs kept in each node's decoded-model cache
CID_BLOCK = 1 << 20     # bytes a CID hashes per block (IPFS's chunk size)
HASH_THREADS = min(8, os.cpu_count() or 1)  # hashlib releases the GIL
# payloads whose device leaves hold fewer bytes keep the host path: a few
# small transfers cost them less than the pack's dispatch and its compile
PACK_MIN_BYTES = CID_BLOCK

# a payload's bytes, as the store holds them: a 1-D ``uint8`` array where
# the image came from the device (``serialize_pytree``)
Block = Union[bytes, bytearray, np.ndarray]


def is_block(obj) -> bool:
    """Whether ``obj`` is a payload's bytes rather than a pytree."""
    return isinstance(obj, (bytes, bytearray)) or (
        isinstance(obj, np.ndarray) and obj.dtype == np.uint8
        and obj.ndim == 1)


# --------------------------------------------------------------------------- #
# Deterministic pytree codec
# --------------------------------------------------------------------------- #

def serialize_pytree(tree) -> Block:
    """The payload image: the JSON header's length (8 bytes, little-endian),
    the header, then every leaf's raw bytes in ``tree_flatten`` order.

    Where the leaves on the device hold ``PACK_MIN_BYTES`` or more, the image
    is built on the device and crosses to the host in one transfer
    (``_device_image``), landing as a read-only ``uint8`` array that nothing
    copies again. Otherwise each leaf comes to the host and is written once
    into one ``bytearray``. Both give the same bytes."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    on_device = [l for l in leaves if isinstance(l, jax.Array)]
    if (sum(l.nbytes for l in on_device) >= PACK_MIN_BYTES
            and all(l.dtype.itemsize in (1, 2, 4) for l in on_device)):
        arrs = [l if isinstance(l, jax.Array) else np.asarray(l)
                for l in leaves]
        return _device_image(_header(tree, treedef, arrs), arrs)
    arrs = [wall.to_host(l) for l in leaves]
    hb = _header(tree, treedef, arrs)
    out = bytearray(8 + len(hb) + sum(a.nbytes for a in arrs))
    out[:8] = len(hb).to_bytes(8, "little")
    off = 8 + len(hb)
    out[8:off] = hb
    view = memoryview(out)
    for a in arrs:
        view[off:off + a.nbytes] = np.ascontiguousarray(a).reshape(-1).view(
            np.uint8)
        off += a.nbytes
    return out


def _header(tree, treedef, arrs) -> bytes:
    return json.dumps({
        "treedef": str(treedef),
        "leaves": [{"dtype": str(a.dtype), "shape": list(a.shape)}
                   for a in arrs],
        "paths": [_path_str(p) for p, _ in
                  jax.tree_util.tree_flatten_with_path(tree)[0]],
    }, sort_keys=True).encode()


def _device_image(hb: bytes, arrs) -> np.ndarray:
    """The image of ``serialize_pytree`` built on the device as 32-bit
    little-endian words and brought to the host in one transfer; the host
    views the words' first bytes. The header and the host leaves go up as
    words of their bytes (the image's other bytes are data too), so the
    pack compiles once per pytree structure, not once per value."""
    head = len(hb).to_bytes(8, "little") + hb
    layout, host_words, dev = [], [], []
    off = 0
    for seg in [head, *arrs]:
        if isinstance(seg, jax.Array):
            layout.append((off, seg.nbytes, True))
            dev.append(seg)
            off += seg.nbytes
            continue
        if not isinstance(seg, bytes):
            seg = np.ascontiguousarray(seg).tobytes()
        padded = bytes(off % 4) + seg
        host_words.append(np.frombuffer(
            padded + bytes(-len(padded) % 4), "<u4"))
        layout.append((off, len(seg), False))
        off += len(seg)
    words = _pack(tuple(layout), _n_words(off), host_words, dev)
    image = np.asarray(words).view(np.uint8)[:off]   # the one transfer
    del words   # the device copy goes as soon as its host copy lands
    wall.count("host_syncs")
    wall.count("d2h_bytes", _n_words(off) * 4)
    return image


def _words(x) -> jax.Array:
    """``x``'s raw bytes as little-endian 32-bit words, the last one padded
    with zeros. Narrow elements combine across a swapped or transposed
    axis: an axis of 2 or 4 elements in the lanes would be padded to 128 in
    the TPU's layout."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    size = x.dtype.itemsize
    if size == 4:
        return lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
    per = 4 // size                  # elements in a word
    u = lax.bitcast_convert_type(x, jnp.uint8 if size == 1 else jnp.uint16)
    n = u.size
    if u.ndim >= 2 and u.shape[-1] % per == 0:
        # a word's elements are neighbours in a row
        t = jnp.swapaxes(u.reshape(-1, u.shape[-1] // per, per), 1, 2)
        parts = [t[:, b, :] for b in range(per)]
    else:
        # row i of ``u`` holds words i*_LANES .. (i+1)*_LANES - 1
        u = jnp.pad(u.reshape(-1), (0, -n % (per * _LANES)))
        t = u.reshape(-1, per * _LANES).T.reshape(_LANES, per, -1)
        parts = [t[:, b, :].T for b in range(per)]
    w = parts[0].astype(jnp.uint32)
    for b in range(1, per):
        w = w | (parts[b].astype(jnp.uint32) << (8 * size * b))
    return w.reshape(-1)[:_n_words(n * size)]


def _pack_words(layout, n_words, host_words, dev):
    """The image's words: each segment's words shifted to its byte offset
    and OR-ed in (segments meet only inside the words they share, where each
    is zero in the others' bytes)."""
    img = jnp.zeros((n_words,), jnp.uint32)
    host_words, dev = iter(host_words), iter(dev)
    for off, nbytes, on_device in layout:
        if on_device:
            w, r = _words(next(dev)), off % 4
            if r:
                w = jnp.concatenate([w, jnp.zeros((1,), jnp.uint32)])
                w = (w << 8 * r) | jnp.concatenate(
                    [jnp.zeros((1,), jnp.uint32), w[:-1]]) >> (32 - 8 * r)
                w = w[:_n_words(r + nbytes)]
        else:
            w = next(host_words)
        k = off // 4
        img = lax.dynamic_update_slice(
            img, lax.slice(img, (k,), (k + w.shape[0],)) | w, (k,))
    return img


def _n_words(nbytes: int) -> int:
    return -(-nbytes // 4)


_LANES = 128
_pack = jax.jit(_pack_words, static_argnums=(0, 1))


def _path_str(path) -> str:
    return jax.tree_util.keystr(path)


def deserialize_pytree(data, like=None):
    """If ``like`` (a pytree prototype) is given, reconstruct its structure;
    otherwise return a flat dict path -> array. The arrays are read-only
    views of ``data``, not copies."""
    hlen = int.from_bytes(data[:8], "little")
    header = json.loads(bytes(data[8:8 + hlen]).decode())
    off = 8 + hlen
    arrs = []
    for spec in header["leaves"]:
        dt = np.dtype(spec["dtype"])
        n = int(np.prod(spec["shape"])) if spec["shape"] else 1
        a = np.frombuffer(data, dtype=dt, count=n, offset=off)
        a.flags.writeable = False
        arrs.append(a.reshape(spec["shape"]))
        off += n * dt.itemsize
    if like is not None:
        treedef = jax.tree_util.tree_structure(like)
        return jax.tree_util.tree_unflatten(treedef, arrs)
    return dict(zip(header["paths"], arrs))


def compute_cid(data) -> str:
    """The CID of a payload: the SHA-256 of its bytes or, past one
    ``CID_BLOCK``, the SHA-256 of its blocks' SHA-256 digests (a one-level
    Merkle DAG, as IPFS chunks large content), the blocks hashed on a few
    threads. The prefixes keep the two forms apart."""
    if len(data) <= CID_BLOCK:
        return "bafy" + hashlib.sha256(data).hexdigest()
    view = memoryview(data).cast("B")
    with ThreadPoolExecutor(HASH_THREADS) as pool:
        digests = pool.map(
            lambda i: hashlib.sha256(view[i:i + CID_BLOCK]).digest(),
            range(0, len(view), CID_BLOCK))
        return "bafb" + hashlib.sha256(b"".join(digests)).hexdigest()


# --------------------------------------------------------------------------- #
# Store nodes + network
# --------------------------------------------------------------------------- #

class StoreNode:
    """One per silo (hosted on the aggregator node, paper §3.4.2)."""

    def __init__(self, node_id: str, root: Optional[str] = None):
        self.node_id = node_id
        self.root = root
        self.network: Optional["StoreNetwork"] = None
        # cid -> its bytes, one buffer shared with the decoded models that
        # view it (never a second copy of a payload)
        self._blocks: Dict[str, Block] = {}
        self._pins: set = set()
        self._peers: List["StoreNode"] = []
        self._lock = threading.Lock()
        # decoded-model cache, keyed (cid, resolved_base): a delta envelope's
        # decoded form depends on its base chain, so the base CID is part of
        # the identity; _decoded_cids indexes cid -> full key (1:1 — content
        # addressing fixes the base a cid resolves against)
        self._decoded: "OrderedDict[Tuple[str, str], Any]" = OrderedDict()
        self._decoded_cids: Dict[str, Tuple[str, str]] = {}
        self._wire_decoder: Optional[Callable] = None
        self._prefetched: set = set()
        self._pending_net_time = 0.0
        self.stats = StatsView("store", node_id)
        if root:
            os.makedirs(root, exist_ok=True)

    @property
    def fabric(self):
        return self.network.fabric if self.network is not None else None

    def wire_decoder(self) -> Callable:
        """Node-bound ``repro.core.wire`` decoder: delta envelopes resolve
        their base chain through this node's decoded cache, fetching missing
        base CIDs over the fabric like any other content."""
        if self._wire_decoder is None:
            from repro.core.wire import decode_store

            def _dec(flat):
                return decode_store(
                    flat, resolver=lambda bcid: self.get_decoded(bcid, _dec))

            self._wire_decoder = _dec
        return self._wire_decoder

    # -- network wiring ---------------------------------------------------- #
    def connect(self, peer: "StoreNode"):
        if peer is not self and peer not in self._peers:
            self._peers.append(peer)

    # -- API ---------------------------------------------------------------- #
    def put(self, obj, *, pin: bool = True) -> str:
        """Store ``obj``, a pytree or a payload's bytes (a ``Block``:
        ``bytes``, ``bytearray`` or a 1-D ``uint8`` array, as
        ``serialize_pytree`` returns), and return its CID."""
        with wall.span("publish.put", silo=self.node_id):
            return self._put(obj, pin)

    def _put(self, obj, pin: bool) -> str:
        data = obj
        if not is_block(obj):
            with wall.span("publish.serialize", silo=self.node_id):
                data = serialize_pytree(obj)
            if isinstance(data, np.ndarray):
                wall.count("packed_puts")
        with wall.span("publish.hash", silo=self.node_id):
            cid = compute_cid(data)
        with self._lock:
            self._hold(cid, data)
            if pin:
                self._pins.add(cid)
            self.stats["puts"] += 1
            self.stats["bytes_stored"] += len(data)
        if self.root:
            with open(os.path.join(self.root, cid), "wb") as f:
                f.write(data)
        fab = self.fabric
        if fab is not None:
            fab.publish(cid, self.node_id, len(data))
        return cid

    def has(self, cid: str) -> bool:
        return cid in self._blocks or (
            self.root and os.path.exists(os.path.join(self.root, cid)))

    def _hold(self, cid: str, data: Block) -> None:
        """Keep ``data`` as ``cid``'s block (lock held); new bytes count in."""
        if cid not in self._blocks:
            wall.count("store_bytes_in", len(data))
        self._blocks[cid] = data

    def held_bytes(self) -> int:
        """Bytes of the blocks this node holds in memory."""
        with self._lock:
            return sum(len(d) for d in self._blocks.values())

    def read_local(self, cid: str) -> Optional[Block]:
        """Local blocks / disk only — never touches the network."""
        with self._lock:
            if cid in self._blocks:
                return self._blocks[cid]
        if self.root:
            p = os.path.join(self.root, cid)
            if os.path.exists(p):
                with open(p, "rb") as f:
                    return f.read()
        return None

    def serve_bytes(self, cid: str) -> Optional[Block]:
        """Serve a block set to a remote peer (counts egress accounting)."""
        data = self.read_local(cid)
        if data is not None:
            with self._lock:
                self.stats["gets"] += 1
                self.stats["bytes_out"] += len(data)
        return data

    def ingest(self, cid: str, data: Block, *, prefetched: bool = False):
        """Store pushed/fetched bytes locally (gossip replica or prefetch
        landing). Verifies content addressing; no-op if already present."""
        if compute_cid(data) != cid:
            raise IOError(f"integrity failure ingesting {cid} on "
                          f"{self.node_id}")
        with self._lock:
            if cid not in self._blocks:
                self._hold(cid, data)
                self.stats["bytes_in"] += len(data)
                # a demand fetch that raced us in already paid for these
                # bytes — only a genuinely landing prefetch earns the credit
                if prefetched:
                    self._prefetched.add(cid)
        fab = self.fabric
        if fab is not None:
            fab.add_provider(cid, self.node_id)

    def drain_transfer_time(self) -> float:
        """Simulated seconds of WAN transfer accumulated since the last
        drain; the orchestrator folds this into its scheduled durations."""
        with self._lock:
            t, self._pending_net_time = self._pending_net_time, 0.0
        return t

    def get_bytes(self, cid: str) -> Block:
        data = self.read_local(cid)
        if data is not None:
            with self._lock:
                self.stats["gets"] += 1
            return data
        fab = self.fabric
        if fab is not None:
            return self._fetch_via_fabric(cid, fab)
        # no fabric: legacy instantaneous DHT-ish peer fetch
        for peer in self._peers:
            if peer.has(cid):
                data = peer.get_bytes(cid)
                if compute_cid(data) != cid:  # integrity check
                    raise IOError(f"integrity failure fetching {cid} "
                                  f"from {peer.node_id}")
                with self._lock:
                    self._hold(cid, data)
                    self.stats["peer_fetches"] += 1
                    self.stats["bytes_fetched"] += len(data)
                return data
        raise KeyError(f"CID {cid} not found on {self.node_id} or peers")

    def _fetch_via_fabric(self, cid: str, fab) -> Block:
        """Pull over the WAN fabric: nearest reachable replica, integrity
        check, link-time charge, replica/reroute accounting."""
        from repro.net.fabric import UnreachableError
        tried: tuple = ()
        while True:
            src_id = fab.best_provider(self.node_id, cid, exclude=tried)
            if src_id is None:
                if fab.has_unreachable_provider(self.node_id, cid,
                                                exclude=tried):
                    raise UnreachableError(
                        f"CID {cid} unreachable from {self.node_id}: every "
                        f"provider is partitioned away or down")
                raise KeyError(f"CID {cid} not found on {self.node_id} "
                               f"or any reachable provider")
            peer = self.network.nodes.get(src_id) if self.network else None
            data = peer.serve_bytes(cid) if peer is not None else None
            if data is None:
                # stale provider record (gc'd or dropped node)
                fab.drop_provider(cid, src_id)
                tried = tried + (src_id,)
                continue
            if compute_cid(data) != cid:
                raise IOError(f"integrity failure fetching {cid} "
                              f"from {src_id}")
            origin = fab.origin(cid)
            if src_id == origin:
                kind = "fetch"
            elif origin is not None and \
                    not fab.reachable(self.node_id, origin):
                kind = "reroute"     # failover: origin gone, replica serves
            else:
                kind = "replica"     # replica was simply nearer
            charged = fab.transfer(src_id, self.node_id, cid, len(data),
                                   kind=kind)
            with self._lock:
                self._hold(cid, data)
                self.stats["peer_fetches"] += 1
                self.stats["bytes_fetched"] += len(data)
                self.stats["bytes_in"] += len(data)
                self.stats["fetch_time"] += charged
                self._pending_net_time += charged
                if kind != "fetch":
                    self.stats["replica_hits"] += 1
            fab.add_provider(cid, self.node_id)
            return data

    def get(self, cid: str, like=None):
        return deserialize_pytree(self.get_bytes(cid), like)

    # -- decoded-model cache (lock held for all three helpers) -------------- #
    def _cache_lookup(self, cid: str):
        """Hit path: returns the cached object or None (updates stats)."""
        key = self._decoded_cids.get(cid)
        if key is None:
            return None
        self.stats["decode_hits"] += 1
        if cid in self._prefetched:
            # one hit per prefetched CID: "the prefetch was useful"
            self.stats["prefetch_hits"] += 1
            self._prefetched.discard(cid)
        self._decoded.move_to_end(key)
        return self._decoded[key]

    def _cache_insert(self, cid: str, obj):
        key = (cid, getattr(obj, "base_cid", "") or "")
        self.stats["decodes"] += 1
        self._decoded[key] = obj
        self._decoded_cids[cid] = key
        while len(self._decoded) > DECODED_CACHE_MAX:
            (ecid, _), _ = self._decoded.popitem(last=False)
            self._decoded_cids.pop(ecid, None)
            self._prefetched.discard(ecid)

    def get_decoded(self, cid: str, decoder: Callable):
        """Zero-copy exchange: fetch + ``decoder(payload)`` once per CID.

        Content addressing makes blocks immutable, so the decoded form (e.g.
        the unpacked int8 payload of a peer model) is safely cached: a model
        pulled by k scorers and then re-pulled for aggregation is
        deserialized exactly once on this node (``stats['decodes']``); the
        other k-1+ touches are ``stats['decode_hits']``. Bounded LRU keyed
        on ``(cid, resolved_base)``."""
        with self._lock:
            hit = self._cache_lookup(cid)
            if hit is not None:
                return hit
        obj = decoder(self.get(cid))
        with self._lock:
            # decode ran unlocked: a concurrent miss may have won the race —
            # keep its object so all callers share one decoded model
            hit = self._cache_lookup(cid)
            if hit is not None:
                return hit
            self._cache_insert(cid, obj)
        return obj

    def has_decoded(self, cid: str) -> bool:
        with self._lock:
            return cid in self._decoded_cids

    def warm_decoded(self, cid: str, decoder: Callable):
        """Prefetch landing: decode a locally-present CID into the cache and
        mark it, so the eventual consumer's hit counts as a prefetch hit. If
        something already decoded it, leave the attribution alone."""
        with self._lock:
            if cid in self._decoded_cids:
                return
        data = self.read_local(cid)
        if data is None:
            return
        obj = decoder(deserialize_pytree(data))
        with self._lock:
            if cid not in self._decoded_cids:
                self._cache_insert(cid, obj)
                self._prefetched.add(cid)

    def pin(self, cid: str):
        self._pins.add(cid)

    def gc(self):
        """Drop unpinned blocks (IPFS gc)."""
        with self._lock:
            for cid in list(self._blocks):
                if cid not in self._pins:
                    wall.count("store_bytes_dropped",
                               len(self._blocks.pop(cid)))

    def replicas(self) -> set:
        """CIDs this node holds but did not put itself: fetched, gossiped or
        prefetched copies."""
        with self._lock:
            return {*self._blocks, *self._decoded_cids} - self._pins

    def base_of(self, cid: str) -> str:
        """The delta base a held CID references ('' for none, or where this
        node holds neither the CID's blocks nor its decoded model)."""
        with self._lock:
            key = self._decoded_cids.get(cid)
            if key is not None:
                return key[1]
            data = self._blocks.get(cid)
        if data is None:
            return ""
        from repro.core.wire import base_cid_of_store
        return base_cid_of_store(deserialize_pytree(data))

    def retain(self, keep, *, with_bases: bool = False) -> List[str]:
        """Drop from memory every CID outside ``keep`` (with ``with_bases``,
        also outside the delta base chains of ``keep``'s CIDs): its blocks,
        its pin, its decoded model, and, where no file on disk still serves
        it, this node's provider record in the fabric. Files under ``root``
        stay. Returns the CIDs dropped."""
        with wall.span("store.retain", silo=self.node_id):
            live = set(keep)
            if with_bases:
                for cid in list(live):
                    base = self.base_of(cid)
                    while base and base not in live:
                        live.add(base)
                        base = self.base_of(base)
            with self._lock:
                dropped = [c for c in {*self._blocks, *self._decoded_cids}
                           if c not in live]
                for cid in dropped:
                    data = self._blocks.pop(cid, None)
                    if data is not None:
                        wall.count("store_bytes_dropped", len(data))
                    self._pins.discard(cid)
                    self._decoded.pop(self._decoded_cids.pop(cid, None), None)
                    self._prefetched.discard(cid)
            fab = self.fabric
            if fab is not None:
                for cid in dropped:
                    if not self.has(cid):
                        fab.drop_provider(cid, self.node_id)
        return dropped


class StoreNetwork:
    """Fully-connected private swarm of silo store nodes. Attach a
    ``repro.net.NetFabric`` to make transfers cost simulated time."""

    def __init__(self, fabric=None):
        self.nodes: Dict[str, StoreNode] = {}
        self.fabric = fabric

    def attach_fabric(self, fabric) -> None:
        """Install the WAN fabric; existing nodes and their blocks are
        registered/published so provider records match reality."""
        self.fabric = fabric
        for node in self.nodes.values():
            fabric.register_node(node.node_id)
            for cid, data in node._blocks.items():
                fabric.publish(cid, node.node_id, len(data))

    def add_node(self, node_id: str, root: Optional[str] = None) -> StoreNode:
        node = StoreNode(node_id, root)
        node.network = self
        for other in self.nodes.values():
            node.connect(other)
            other.connect(node)
        self.nodes[node_id] = node
        if self.fabric is not None:
            self.fabric.register_node(node_id)
        return node

    def drop_node(self, node_id: str):
        """Simulate a node failure: disconnect it from the swarm."""
        node = self.nodes.pop(node_id)
        for other in self.nodes.values():
            if node in other._peers:
                other._peers.remove(node)
        if self.fabric is not None:
            self.fabric.node_down(node_id)
        return node
