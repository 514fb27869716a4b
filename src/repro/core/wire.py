"""repro.core.wire — the single model exchange codec (``ModelEnvelope``).

Every model that crosses a silo boundary — store puts, gossip replicas,
prefetches, the legacy in-memory compression API — is encoded and decoded
here, and nowhere else. An envelope is versioned and self-describing:

  method        payload                                     base chain
  ----------    ----------------------------------------    ----------
  raw           f32 flat vector                             —
  int8          dense per-tile int8 (quant.py layout)       —
  int8-delta    tile-sparse int8 of (vec - base)            ``base_cid``
  topk-delta    magnitude top-k of (vec - base)             ``base_cid``

Delta methods reference their base by CID: the receiver resolves the chain
through its store's decoded cache (``DecodedModel.vec()``), fetching missing
bases over the fabric like any other CID. The sender computes its delta
against the *decoded* base (what receivers reconstruct), so sender and
receiver share bit-identical base vectors and quantization error never
compounds across the chain.

``int8-delta`` is tile-sparse: quantization tiles whose delta is entirely
zero after quantization (always true for alignment padding) are elided, and
— when the base is known — so are tiles whose delta amplitude stays within
``delta_rtol`` quantization steps of the base tile (changes below the int8
wire format's own noise floor are not representable at q8 fidelity anyway).
That is what cuts steady-state WAN bytes vs whole-model int8.

Reconstruction of int8 deltas is fused (``kernels/q8agg.add_q8_delta``): the
int8 delta applies onto the base vector in one VMEM pass without ever
materializing the dequantized f32 delta.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.obs import wall

WIRE_VERSION = 1
METHODS = ("raw", "int8", "int8-delta", "topk-delta")
QT = ops.QTILE                 # quantization tile (scale granularity)
# DecodedModel.vec() keeps its reconstruction only up to this size. A model
# of 516M parameters reconstructs to 2 GB of f32 on the device; kept per
# decoded CID in every store node's cache, those vectors fill a 16 GB chip
# within two rounds. Larger models reconstruct again on each use instead.
VEC_MEMO_MAX_BYTES = 256 << 20

# Exact keystr paths of envelope fields as serialized by store.serialize_pytree
# (exact-match lookups: substring matching broke on params literally named "q").
_kp = lambda name: f"['{name}']"
K_WIRE = _kp("__wire__")
K_METHOD = _kp("__method__")
K_N = _kp("n")
K_BASE = _kp("base_cid")
K_Q = _kp("q")
K_SCALES = _kp("scales")
K_TILES = _kp("tiles")
K_IDX = _kp("idx")
K_VALS = _kp("vals")
K_VEC = _kp("vec")

_ARRAY_FIELDS = ("q", "scales", "tiles", "idx", "vals", "vec")

# legacy compression-method names -> wire methods
_METHOD_ALIASES = {"none": "raw", "raw": "raw", "int8": "int8",
                   "int8-delta": "int8-delta", "topk": "topk-delta",
                   "topk-delta": "topk-delta"}


def resolve_method(compression: str) -> str:
    """Map a ``FedConfig.compression`` value onto a wire method."""
    try:
        return _METHOD_ALIASES[compression]
    except KeyError:
        raise ValueError(f"unknown compression/wire method {compression!r} "
                         f"(choose from {sorted(_METHOD_ALIASES)})") from None


def _padded_n(n: int) -> int:
    """Length of the dense quantized form of an n-vector (quant.py padding)."""
    return n + (-n) % ops.QUANT_BLOCK


class ModelEnvelope:
    """One wire-encoded model: method + payload arrays + base reference."""

    __slots__ = ("method", "n", "base_cid", "q", "scales", "tiles", "idx",
                 "vals", "vec")

    def __init__(self, method: str, n: int, *, base_cid: str = "",
                 q=None, scales=None, tiles=None, idx=None, vals=None,
                 vec=None):
        if method not in METHODS:
            raise ValueError(f"unknown wire method {method!r}")
        self.method = method
        self.n = int(n)
        self.base_cid = base_cid or ""
        self.q = q
        self.scales = scales
        self.tiles = tiles
        self.idx = idx
        self.vals = vals
        self.vec = vec

    @property
    def is_delta(self) -> bool:
        return self.method.endswith("-delta")

    def nbytes(self) -> int:
        """True payload size: the bytes this envelope puts on the wire."""
        return sum(np.asarray(getattr(self, f)).nbytes
                   for f in _ARRAY_FIELDS if getattr(self, f) is not None)

    def to_store(self) -> Dict[str, np.ndarray]:
        """Self-describing pytree for ``store.put`` (deterministic codec)."""
        out = {"__wire__": np.asarray(WIRE_VERSION, np.int64),
               "__method__": np.asarray(self.method),
               "n": np.asarray(self.n, np.int64)}
        if self.base_cid:
            out["base_cid"] = np.asarray(self.base_cid)
        for f in _ARRAY_FIELDS:
            a = getattr(self, f)
            if a is not None:
                out[f] = wall.to_host(a)
        return out

    # -- reconstruction ----------------------------------------------------- #
    def reconstruct(self, base_vec=None, *, force: str = "auto"):
        """Flat f32 [n] model. ``base_vec`` overrides the base chain (delta
        with no base given reconstructs against zeros). ``force='ref'``
        selects the unfused oracle path (bit-parity testing)."""
        n = self.n
        if self.method == "raw":
            return jnp.asarray(self.vec, jnp.float32)
        if self.method == "int8":
            return ops.dequantize(jnp.asarray(self.q),
                                  jnp.asarray(self.scales), n, force=force)
        base = (jnp.zeros((n,), jnp.float32) if base_vec is None
                else jnp.asarray(base_vec, jnp.float32)[:n])
        if self.method == "topk-delta":
            return base.at[jnp.asarray(self.idx)].add(
                jnp.asarray(self.vals, jnp.float32))
        # int8-delta: one fused base + s*q pass (no f32 delta is ever
        # materialized)
        if np.asarray(self.tiles).shape[0] == 0:
            return base
        qd, sd = dense_q8_delta(self.tiles, self.q, self.scales, n)
        return ops.add_q8_delta(base, qd, sd, n, force=force)


def dense_q8_delta(tiles, q, scales, n: int):
    """Scatter an int8-delta's kept tiles into the dense quant grid of an
    n-vector, on the host (the payload arrived there): (q int8 [Np],
    scales f32 [Np/QT]), zero in the tiles that were dropped."""
    total = _padded_n(n) // QT
    qd = np.zeros((total, QT), np.int8)
    qd[np.asarray(tiles)] = np.asarray(q).reshape(-1, QT)
    sd = np.zeros((total,), np.float32)
    sd[np.asarray(tiles)] = np.asarray(scales)
    return qd.reshape(-1), sd


# --------------------------------------------------------------------------- #
# Encoding
# --------------------------------------------------------------------------- #

def encode_vec(vec, method: str, *, base_vec=None, base_cid: str = "",
               topk_frac: float = 0.01,
               delta_rtol: float = 1.0) -> ModelEnvelope:
    """Encode a flat f32 [n] model vector.

    Delta methods encode (vec - base_vec); without a base they fall back to
    a whole-model envelope (``int8-delta`` -> ``int8``) or a delta against
    zeros (``topk-delta``, the legacy sparsify-the-model semantics)."""
    method = resolve_method(method)
    vec = jnp.asarray(vec, jnp.float32)
    n = int(vec.shape[0])
    if method == "raw":
        return ModelEnvelope("raw", n, vec=vec)
    if method == "int8" or (method == "int8-delta" and base_vec is None):
        q, s, _ = ops.quantize(vec)
        return ModelEnvelope("int8", n, q=q, scales=s)
    if base_vec is None:
        base_cid = ""
        delta = vec
    else:
        delta = vec - jnp.asarray(base_vec, jnp.float32)[:n]
    if method == "topk-delta":
        k = max(1, int(n * topk_frac))
        idx = jnp.argsort(-jnp.abs(delta))[:k].astype(jnp.int32)
        return ModelEnvelope("topk-delta", n, base_cid=base_cid,
                             idx=idx, vals=delta[idx])
    # int8-delta: dense quantize, then tile-sparse elision
    q, s, _ = ops.quantize(delta)
    qt = wall.to_host(q).reshape(-1, QT)
    s_np = wall.to_host(s)
    keep = np.abs(qt).max(axis=1) > 0        # drops padding + exact zeros
    if delta_rtol > 0:
        dpad = np.zeros((qt.shape[0] * QT,), np.float32)
        dpad[:n] = wall.to_host(delta)
        damax = np.abs(dpad).reshape(-1, QT).max(axis=1)
        bpad = np.zeros_like(dpad)
        bpad[:n] = wall.to_host(base_vec, np.float32)[:n] \
            if base_vec is not None else 0.0
        bamax = np.abs(bpad).reshape(-1, QT).max(axis=1)
        # noise floor: one quantization step of the base tile — deltas that
        # never exceed delta_rtol steps are invisible at q8 wire fidelity
        keep &= damax > delta_rtol * bamax / 127.0
    tiles = np.nonzero(keep)[0].astype(np.int32)
    return ModelEnvelope("int8-delta", n, base_cid=base_cid,
                         q=qt[keep].reshape(-1),
                         scales=s_np[keep].astype(np.float32), tiles=tiles)


def encode_update(params, fed, *, spec=None,
                  base: Tuple[str, Optional[jnp.ndarray]] = ("", None)
                  ) -> ModelEnvelope:
    """Encode a silo's params per its ``FedConfig`` (the round submit path).
    ``base`` is ``(base_cid, decoded base vector)`` for delta coding."""
    vec, _ = ops.flatten_pytree(params, spec)
    base_cid, base_vec = base
    return encode_vec(vec, resolve_method(fed.compression),
                      base_vec=base_vec, base_cid=base_cid,
                      topk_frac=fed.topk_frac,
                      delta_rtol=getattr(fed, "delta_rtol", 1.0))


def chain_depth_of(node, cid: str, *, max_links: int = 64) -> int:
    """Delta links under ``cid`` on a store node's local blocks (0 = whole
    model). This is the walk a late joiner / post-reorg catch-up performs;
    ``FedConfig.keyframe_every`` bounds it by shipping periodic whole-model
    keyframes. Stops where the chain leaves the node."""
    from repro.core.store import deserialize_pytree
    depth, cur = 0, cid
    while depth < max_links:
        data = node.read_local(cur)
        if data is None:
            break
        base = base_cid_of_store(deserialize_pytree(data))
        if not base:
            break
        depth += 1
        cur = base
    return depth


def base_cid_of_store(flat: Dict) -> str:
    """The delta-base CID a store payload references ('' when none).
    Accepts both plain-key payload dicts (``to_store`` output) and
    *serialized* payloads (keystr keys, as returned by
    ``store.deserialize_pytree`` — the gossip base-chain walk)."""
    b = flat.get(K_BASE)
    if b is None:
        b = flat.get("base_cid")
    return str(np.asarray(b)) if b is not None else ""


# --------------------------------------------------------------------------- #
# Decoded-model representation (zero-copy exchange path)
# --------------------------------------------------------------------------- #

class DecodedModel:
    """A peer model decoded from its wire envelope, kept in exchange form.

    Quantized payloads stay as (q int8, scales) host arrays, so a store
    node's decoded cache holds no device memory and the fused kernels
    consume them without ever materializing the f32 vector; ``vec()``
    reconstructs lazily and memoizes up to ``VEC_MEMO_MAX_BYTES``. Delta
    envelopes resolve their base chain through
    ``resolver`` (the store node's decoded cache, which fetches missing base
    CIDs over the fabric), then apply the int8 delta with the fused
    ``add_q8_delta`` kernel."""

    __slots__ = ("n", "method", "base_cid", "q", "scales", "tiles", "idx",
                 "vals", "_vec", "_resolver")

    def __init__(self, n: int, *, q=None, scales=None, vec=None,
                 method: Optional[str] = None, base_cid: str = "",
                 tiles=None, idx=None, vals=None,
                 resolver: Optional[Callable[[str], "DecodedModel"]] = None):
        self.n = int(n)
        self.q = q
        self.scales = scales
        self.tiles = tiles
        self.idx = idx
        self.vals = vals
        self.base_cid = base_cid or ""
        self._vec = vec
        self._resolver = resolver
        if method is None:  # legacy construction sites: int8 payload or vec
            method = "int8" if q is not None else "raw"
        self.method = method

    @property
    def is_q8(self) -> bool:
        """Whole-model int8: directly consumable by the fused aggregation /
        Gram kernels (delta payloads must reconstruct first)."""
        return self.method == "int8" and self.q is not None

    def resolve_base(self) -> None:
        """Fetch and decode the delta base chain without reconstructing
        anything, so an unreachable base surfaces now (KeyError/IOError)."""
        if self.base_cid and self._vec is None:
            self._base().resolve_base()

    def _base(self) -> "DecodedModel":
        if self._resolver is None:
            raise KeyError(f"delta base {self.base_cid} needs a "
                           "store-bound resolver to reconstruct")
        return self._resolver(self.base_cid)

    def _envelope(self) -> ModelEnvelope:
        return ModelEnvelope(self.method, self.n, base_cid=self.base_cid,
                             q=self.q, scales=self.scales, tiles=self.tiles,
                             idx=self.idx, vals=self.vals, vec=self._vec)

    def vec(self):
        """Flat f32 [n] view of the model (reconstructed once, then cached
        when it is at most ``VEC_MEMO_MAX_BYTES``). Delta models resolve
        ``base_cid`` recursively through the resolver; a missing base
        without a resolver is an error."""
        if self._vec is not None:
            return self._vec
        base = self._base().vec() if self.base_cid else None
        vec = self._envelope().reconstruct(base)
        if 4 * self.n <= VEC_MEMO_MAX_BYTES:
            self._vec = vec
        return vec


def decode_store(flat: Dict[str, np.ndarray],
                 resolver: Optional[Callable] = None) -> DecodedModel:
    """Store payload (keystr -> array dict) -> DecodedModel.

    Handles v1 ``__wire__`` envelopes, the legacy pre-wire int8 envelope
    (``{"__method__": "int8", "q", "scales", "n"}``), and raw parameter
    payloads (flattened to one f32 vector in jax tree order)."""
    if K_WIRE in flat:
        version = int(np.asarray(flat[K_WIRE]))
        if version > WIRE_VERSION:
            raise ValueError(f"wire envelope v{version} is newer than this "
                             f"codec (v{WIRE_VERSION})")
        method = str(np.asarray(flat[K_METHOD]))
        n = int(np.asarray(flat[K_N]))
        base_cid = str(np.asarray(flat[K_BASE])) if K_BASE in flat else ""
        j = lambda key: np.asarray(flat[key]) if key in flat else None
        if method == "raw":
            return DecodedModel(n, vec=jnp.asarray(flat[K_VEC], jnp.float32),
                                method="raw")
        if method == "int8":
            return DecodedModel(n, q=j(K_Q), scales=j(K_SCALES),
                                method="int8")
        if method == "int8-delta":
            return DecodedModel(n, q=j(K_Q), scales=j(K_SCALES),
                                tiles=j(K_TILES), method="int8-delta",
                                base_cid=base_cid, resolver=resolver)
        if method == "topk-delta":
            return DecodedModel(n, idx=j(K_IDX), vals=j(K_VALS),
                                method="topk-delta", base_cid=base_cid,
                                resolver=resolver)
        raise ValueError(f"unknown wire method {method!r} in envelope")
    legacy = flat.get(K_METHOD)
    if legacy is not None and str(np.asarray(legacy)) == "int8":
        return DecodedModel(int(np.asarray(flat[K_N])),
                            q=np.asarray(flat[K_Q]),
                            scales=np.asarray(flat[K_SCALES]))
    if not flat:
        return DecodedModel(0, vec=jnp.zeros((0,), jnp.float32))
    vec = jnp.concatenate([jnp.ravel(jnp.asarray(v)).astype(jnp.float32)
                           for v in flat.values()])
    return DecodedModel(int(vec.shape[0]), vec=vec)


def decode_flat(flat: Dict[str, np.ndarray]) -> DecodedModel:
    """Resolver-less decode (non-delta payloads / tests)."""
    return decode_store(flat)


def _envelope_from_store(flat: Dict) -> Optional[ModelEnvelope]:
    """Parse a plain-key payload dict (pre-serialization form) back into an
    envelope; None when it is not an envelope."""
    if "__wire__" not in flat:
        return None
    g = lambda k: (jnp.asarray(flat[k]) if k in flat else None)
    return ModelEnvelope(str(np.asarray(flat["__method__"])),
                         int(np.asarray(flat["n"])),
                         base_cid=(str(np.asarray(flat["base_cid"]))
                                   if "base_cid" in flat else ""),
                         q=g("q"), scales=g("scales"), tiles=g("tiles"),
                         idx=g("idx"), vals=g("vals"), vec=g("vec"))


# --------------------------------------------------------------------------- #
# Legacy in-memory compression API (repro.core.compression delegates here)
# --------------------------------------------------------------------------- #

def compress_pytree(params, method: str = "int8", *, base=None,
                    topk_frac: float = 0.01) -> Dict:
    """Payload pytree for a params tree; delta-coded iff ``base`` is given."""
    vec, _ = ops.flatten_pytree(params)
    bvec = ops.flatten_pytree(base)[0] if base is not None else None
    m = resolve_method(method)
    if m == "int8" and bvec is not None:
        m = "int8-delta"
    # "__inline__": the base is supplied by the decompress caller, not a CID
    return encode_vec(vec, m, base_vec=bvec, topk_frac=topk_frac,
                      base_cid="__inline__" if bvec is not None else ""
                      ).to_store()


def decompress_pytree(payload: Dict, like, *, base=None):
    """Inverse of ``compress_pytree``; delta payloads reconstruct against
    ``base`` (or ``like`` when no base is passed, the legacy fallback)."""
    env = _envelope_from_store(payload)
    if env is None:
        raise ValueError("not a wire envelope payload")
    _, spec = ops.flatten_pytree(like)
    bvec = None
    if env.base_cid:  # delta vs a caller-supplied base (legacy: like)
        bvec = ops.flatten_pytree(base if base is not None else like)[0]
    return ops.unflatten_pytree(env.reconstruct(bvec), spec)


def payload_bytes(payload) -> int:
    """Total bytes of a payload pytree (envelope or raw params)."""
    return sum(np.asarray(l).nbytes for l in jax.tree.leaves(payload))
