"""Decode-state manager: per-sequence cache consistency + offload accounting.

The decode runtime makes a per-token SplitEE decision at the bandit's
splitting layer, which creates two cache-consistency obligations the
classifier stream never had:

* **Early exit at layer ℓ** — layers > ℓ must not advance their cache
  slots for that step. For the attention ring buffer this costs nothing
  extra: a skipped layer simply leaves its slot for this step unwritten,
  and the ``pos`` validity mask (``pos >= 0 & pos <= cur_index``) excludes
  the hole at every future read, so ``cur_index`` stays the *global* step
  for all layers and RoPE positions stay global. Recurrent states (rwkv6 /
  mamba2) are frozen with a per-sample ``jnp.where`` select. Both are
  implemented inside ``transformer.decode_step_masked``, whose layer loop
  stops at the step's deepest split: layers above it are skipped, not
  masked, and the per-sample freeze acts only below it. This manager owns
  the resulting cache tree and the realized-depth ledger.

* **Mid-generation offload** — the edge ships the split-layer hidden
  through the :class:`OffloadCodec` (a real encode/decode round trip: what
  the cloud computes on is the *reconstruction*, so quantization error is
  visible in the outputs, exactly like the classifier runtimes; the
  identity codec ships the device array as it is) plus the
  per-step ≤ℓ cache-slice update at raw bytes (the cloud needs layers ≤ ℓ
  current to keep decoding; the slice is structured state, shipped
  unquantized). The cloud half (``decode_step_resume``) advances only
  layers > ℓ of offloaded samples — its loop skips the layers at or below
  the shallowest offloaded split — and passes everything else through
  bitwise, so merging its returned tree back IS the edge re-sync.

Wire accounting is exact and closed-form: ``step_slice_bytes`` prices the
per-step cache-slice from a ``jax.eval_shape`` template of a one-slot
cache (attention: one K/V slot + 4 pos bytes per layer; ssm/hybrid: the
full recurrent state per layer), and ``offload_scale_vec`` turns that into
the per-arm wire/raw ratio the controller folds into the paper's
communication term ``o`` — deeper splits ship strictly more slice bytes.
Which layers hold which state follows the family: every layer its own
(dense, moe, ssm); a Mamba2 state per layer plus the shared block's K/V
after every k-th layer (zamba2); or, with ``layer_types``, each layer the
state of its own mixer (granite-4.0-h: a Mamba2 layer its SSM and conv
state, an attention layer its K/V).
"""
from __future__ import annotations

from typing import List, Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.serving.offload_codec import OffloadCodec


def _state_layers(cfg: ModelConfig, key: str) -> np.ndarray:
    """(L,) bool: the layers that hold an entry of cache stack ``key``
    ("ssm" or "attn")."""
    L = cfg.num_layers
    if cfg.layer_types:
        attn = np.array([t == "attention" for t in cfg.layer_kinds()])
        return attn if key == "attn" else ~attn
    if cfg.family == "hybrid" and key == "attn":
        k = cfg.hybrid_attn_every
        return np.arange(L) % k == k - 1
    return np.ones(L, bool)


def layer_state_bytes(cfg: ModelConfig, caches) -> np.ndarray:
    """(L,) bytes of each layer's part of a cache tree (or of its
    ``jax.eval_shape``): one entry of the stack of each state it holds,
    over every row."""
    out = np.zeros(cfg.num_layers, np.int64)
    for key, stack in caches.items():
        out[_state_layers(cfg, key)] += sum(
            int(np.prod(leaf.shape[1:])) * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(stack))
    return out


def per_step_layer_bytes(cfg: ModelConfig) -> np.ndarray:
    """(L,) bytes each layer adds to its cache per decode step.

    Derived from an abstract one-token cache template (``seq_len=1`` makes
    the attention window exactly one slot), so the closed form tracks the
    real cache dtypes/shapes for every family without reimplementing them.
    """
    from repro.models import transformer
    return layer_state_bytes(cfg, jax.eval_shape(
        lambda: transformer.init_caches(cfg, 1, 1)))


def step_slice_bytes(cfg: ModelConfig, depth: int) -> int:
    """Wire bytes of the per-step cache updates for layers 0..depth — the
    slice an offload at split ``depth`` ships so the cloud's copy of the
    edge-computed layers is current."""
    return int(np.cumsum(per_step_layer_bytes(cfg))[depth])


def hidden_raw_bytes(cfg: ModelConfig) -> int:
    """Full-dtype bytes of the (1, D) split-layer hidden payload."""
    return cfg.d_model * np.dtype(cfg.dtype).itemsize


def offload_scale_vec(cfg: ModelConfig,
                      codec: Optional[OffloadCodec]) -> np.ndarray:
    """(L,) per-arm wire/raw byte ratio for the bandit's communication
    term: arm i offloads ``codec(hidden) + slice(≤i)`` wire bytes against a
    raw price of ``hidden + slice(≤i)``. All-ones without a codec."""
    slice_b = np.cumsum(per_step_layer_bytes(cfg)).astype(np.float64)
    raw_h = float(hidden_raw_bytes(cfg))
    if codec is None:
        wire_h = raw_h
    else:
        wire_h = float(codec.row_bytes(1, cfg.d_model,
                                       np.dtype(cfg.dtype).itemsize))
    return (wire_h + slice_b) / (raw_h + slice_b)


class DecodeCacheManager:
    """Owns one push-batch's decode cache tree and its consistency ledger.

    The device tree itself is advanced by ``decode_step_masked`` (edge) and
    ``decode_step_resume`` (cloud resync) — both return full trees that are
    bitwise the input at every coordinate they did not advance, so the
    manager's job is bookkeeping: commit the trees, log realized depths and
    offload decisions per step (the replay tests re-decode from a fresh
    cache against this ledger), run the codec round trip with per-sequence
    error-feedback residuals, and meter wire bytes.
    """

    def __init__(self, cfg: ModelConfig, caches,
                 codec: Optional[OffloadCodec] = None):
        self.cfg = cfg
        self.caches = caches
        self.codec = codec
        b = int(jax.tree.leaves(caches)[0].shape[1])
        self.batch = b
        self._slice_cum = np.cumsum(per_step_layer_bytes(cfg))
        # (L,) bytes of each layer's whole state over the batch: what a
        # layer loop that runs the layer carries through it
        self.layer_bytes = layer_state_bytes(cfg, caches)
        self.realized_depths: List[np.ndarray] = []   # (B,) per step
        self.offloaded: List[np.ndarray] = []         # (B,) bool per step
        self.offloads_per_seq = np.zeros(b, np.int64)
        self.wire_bytes_per_seq = np.zeros(b, np.int64)
        self._residual = None
        if codec is not None and codec.error_feedback:
            self._residual = np.zeros((b, 1, cfg.d_model), np.float32)

    # ------------------------------------------------------------- commits

    def commit_edge(self, new_caches, depths: np.ndarray):
        self.caches = new_caches
        self.realized_depths.append(np.asarray(depths, np.int64).copy())

    def commit_cloud(self, new_caches, active: np.ndarray):
        """The cloud's returned tree passes non-active coordinates through
        bitwise, so committing it wholesale re-syncs the edge cache."""
        self.caches = new_caches
        self.offloaded.append(np.asarray(active, bool).copy())

    def note_no_offload(self):
        self.offloaded.append(np.zeros(self.batch, bool))

    # ------------------------------------------------------------ offloads

    def ship_hidden(self, hidden: np.ndarray, rows: np.ndarray):
        """Codec round trip for the offloaded samples' split-layer hidden.

        hidden: (B, 1, D) host array; rows: int index array of offloading
        samples. Returns ``(decoded_rows, hidden_wire_bytes_per_row)`` —
        the cloud consumes the *decoded* payload, so codec loss is visible
        end to end. With ``error_feedback`` the per-sequence residual is
        folded in and updated. Needs a codec: under the identity the
        session hands the cloud the edge's device array itself, metered at
        ``hidden_raw_bytes``.
        """
        sel = hidden[rows]
        if self._residual is not None:
            enc, decoded, new_res = self.codec.encode_with_feedback(
                sel, self._residual[rows])
            self._residual[rows] = new_res
        else:
            enc = self.codec.encode(sel)
            decoded = self.codec.decode(enc)
        return decoded.astype(hidden.dtype), enc.row_bytes

    def offload_wire_bytes(self, depth: int, hidden_wire: int) -> int:
        """Total metered bytes for one offload at split ``depth``."""
        return int(hidden_wire) + int(self._slice_cum[depth])

    def meter(self, rows: np.ndarray, depths: np.ndarray,
              hidden_wire: int) -> np.ndarray:
        """Per-sample wire bytes for this step's offloads; updates the
        per-sequence ledgers and returns the (len(rows),) byte array."""
        out = np.empty(len(rows), np.int64)
        for j, b in enumerate(rows):
            out[j] = self.offload_wire_bytes(int(depths[b]), hidden_wire)
        self.offloads_per_seq[rows] += 1
        self.wire_bytes_per_seq[rows] += out
        return out
