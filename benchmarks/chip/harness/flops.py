"""Operations and bytes that the algorithm needs, from shapes alone.

These are the benchmark's own counts, kept apart from the program so that
no change to the program can move them.  A kernel's roofline share is the
least time these counts allow on the chip (the larger of FLOPs over the
peak FLOP/s and bytes over the peak bytes/s) over the kernel's device time.

GEAR layout of one compressed chunk of ``n_b`` tokens of one KV head
(``bits``-bit codes, low rank ``r``, outlier share ``s``):

* K and V codes: ``n_b * d * bits / 8`` bytes each;
* K statistics per channel (scale, zero): ``2 * d`` values; V per token:
  ``2 * n_b`` values, each ``stat_bytes``;
* low-rank factors of K and of V: ``(n_b + d) * r`` values each;
* outliers, value + int32 index: K keeps ``2 * ceil(n_b * s / 2)`` per
  channel, V ``2 * ceil(d * s / 2)`` per token.
"""

from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Gear:
    head_dim: int
    chunk: int = 64
    bits: int = 4
    rank: int = 4
    sparsity: float = 0.02
    stat_bytes: int = 2       # bf16 scales, zeros, factors, outlier values

    def outliers(self, n: int) -> int:
        """Outliers kept per vector of ``n`` entries (both extremes)."""
        return 2 * max(1, math.ceil(n * self.sparsity / 2.0))

    def chunk_bytes(self) -> int:
        """Bytes of one chunk of one KV head, as stored."""
        nb, d, sb = self.chunk, self.head_dim, self.stat_bytes
        codes = 2 * nb * d * self.bits // 8
        stats = 2 * d * sb + 2 * nb * sb
        lowrank = 2 * (nb + d) * self.rank * sb
        sparse = (d * self.outliers(nb) + nb * self.outliers(d)) * (sb + 4)
        return codes + stats + lowrank + sparse


def decode_paged_cost(g: Gear, live_chunks: int, rows: int, group: int):
    """(FLOPs, bytes) of one ``gear_decode_paged`` call.

    ``live_chunks``: compressed chunks summed over the call's rows (slot x
    KV head), counting only chunks that hold the row's tokens;
    ``rows``: slots x KV heads; ``group``: query heads per KV head.  Per
    live chunk and row: scores and values (``4 * G * n_b * d``) plus the
    low-rank paths of K and V (``4 * G * r * (n_b + d)``).  Bytes: the
    live chunks, the f32 queries and the f32 outputs (acc, and the
    128-lane max and sum)."""
    nb, d, r, G = g.chunk, g.head_dim, g.rank, group
    flops = live_chunks * (4 * G * nb * d + 4 * G * r * (nb + d))
    nbytes = (live_chunks * g.chunk_bytes()
              + rows * G * d * 4 + rows * G * (d + 2 * 128) * 4)
    return flops, nbytes


def compress_cost(g: Gear, chunks: int, kind: str):
    """(FLOPs, bytes) of compressing ``chunks`` chunk-heads of ``kind``
    ``"k"`` (per-channel statistics) or ``"v"`` (per-token) with
    ``gear_compress``: read the f32 chunk; write the codes, f32 statistics,
    outliers (f32 value + int32 index) and the f32 residual.  The algorithm
    needs no matrix products; its element-wise work is not counted."""
    nb, d = g.chunk, g.head_dim
    if kind == "k":
        stats, sparse = 2 * d * 4, g.outliers(nb) * d * 8
    else:
        stats, sparse = 2 * nb * 4, nb * g.outliers(d) * 8
    per = nb * d * 4 + nb * d * g.bits // 8 + stats + sparse + nb * d * 4
    return 0, chunks * per


@dataclasses.dataclass(frozen=True)
class Dense:
    """A decoder's shapes, for model FLOPs.  Every layer attends fully
    unless ``kinds`` names each layer ``"full"`` or ``"window"`` (a window
    layer attends the last ``window`` keys); the FFN is a dense MLP of
    ``d_ff`` unless ``experts`` > 0: ``top_k`` of them a token, each
    ``expert_ff`` wide, and a router over all."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool
    kinds: tuple[str, ...] = ()     # per layer; empty: all full
    window: int = 0
    experts: int = 0
    top_k: int = 0
    expert_ff: int = 0

    def __post_init__(self):
        if self.kinds and len(self.kinds) != self.layers:
            raise ValueError(f"{len(self.kinds)} layer kinds for {self.layers} layers")
        if set(self.kinds) - {"full", "window"}:
            raise ValueError(f"layer kinds {sorted(set(self.kinds))}: only full and window")
        if "window" in self.kinds and self.window < 1:
            raise ValueError("window layers need a window of at least 1")

    @classmethod
    def from_config(cls, c: dict) -> "Dense":
        H = c["num_attention_heads"]
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=H, kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim", c["hidden_size"] // H),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   gated=c["hidden_act"] == "silu")

    def layer_kinds(self) -> tuple[str, ...]:
        return self.kinds or ("full",) * self.layers

    @property
    def gear_layers(self) -> int:
        """Layers whose K/V the GEAR pool holds: the full ones."""
        return self.layer_kinds().count("full")

    def matmul_params(self) -> int:
        """Weights each token multiplies in the layers (not the head): the
        attention, and the MLP or the router and ``top_k`` experts."""
        d, q, kv = self.d_model, self.heads * self.head_dim, self.kv_heads * self.head_dim
        mats = 3 if self.gated else 2
        if self.experts:
            ffn = self.top_k * mats * d * self.expert_ff + d * self.experts
        else:
            ffn = mats * d * self.d_ff
        return self.layers * (2 * d * q + 2 * d * kv + ffn)

    def _keys(self, context: int) -> int:
        """Keys one token at ``context`` attends, summed over the layers."""
        return sum(context if k == "full" else min(context, self.window)
                   for k in self.layer_kinds())

    def token_flops(self, context: int, logits: bool) -> int:
        """FLOPs of one token that attends ``context`` keys (itself
        included; a window layer ``min(context, window)``), with the output
        head when ``logits``."""
        attn = 4 * self._keys(context) * self.heads * self.head_dim
        head = 2 * self.d_model * self.vocab if logits else 0
        return 2 * self.matmul_params() + attn + head

    def prefill_flops(self, n: int) -> int:
        """A causal prefill of ``n`` tokens with logits at the last one:
        token i (1..n) attends i keys, ``min(i, window)`` on a window layer."""
        w = min(n, self.window)
        windowed = w * (w + 1) // 2 + (n - w) * self.window
        keys = sum(n * (n + 1) // 2 if k == "full" else windowed
                   for k in self.layer_kinds())
        attn = 4 * self.heads * self.head_dim * keys
        return 2 * self.matmul_params() * n + attn + 2 * self.d_model * self.vocab
