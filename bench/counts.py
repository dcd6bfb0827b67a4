"""Operations and bytes that a served request needs, from the
configuration file and the call's shapes.

These are the work the request requires, never what the compiled program
does (no HLO, no ``cost_analysis``), so a later program that skips dead
rows or reads only live KV cannot push a share above its roofline:

- weights are read once per call;
- a decode row reads its K/V over its live positions only (prompt plus the
  tokens generated so far), not over the ring's capacity, and writes one
  new position;
- a row that is done but still sits in a batch counts nothing;
- prefill attention is causal over the prompt, and only the last
  position's logits are needed.

Model FLOPs count a multiply-add as 2.  Bytes are bf16 (2 per element)
for weights, K/V, embeddings and logits.

``Shape`` counts a dense decoder layer.  An architecture module
(``bench/archs/<model_type>.py``) gives its own ``Shape`` with the same
interface: ``from_config``, ``weight_bytes_per_call`` and the calls
``prefill_call``, ``decode_call`` and ``decode_rows``.  Readers and
``run.py`` reach the counts only through those.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Sequence

BYTES = 2          # bf16


@dataclass(frozen=True)
class Shape:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    gated: bool
    qk_norm: bool
    tied: bool

    @classmethod
    def from_config(cls, c: dict) -> "Shape":
        arch = c["architecture"]
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   ff=c["intermediate_size"], vocab=c["vocab_size"],
                   gated=arch["mlp"] == "swiglu", qk_norm=arch["qk_norm"],
                   tied=bool(c["tie_word_embeddings"]))

    # -- parameters --------------------------------------------------------
    @property
    def layer_matmul_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        attn = self.d * q + 2 * self.d * kv + q * self.d
        mlp = (3 if self.gated else 2) * self.d * self.ff
        return attn + mlp

    @property
    def layer_other_params(self) -> int:
        return 2 * self.d + (2 * self.head_dim if self.qk_norm else 0)

    @property
    def head_params(self) -> int:
        return self.d * self.vocab

    @property
    def weight_bytes_per_call(self) -> int:
        """Weights every call reads: all layers, final norm, output head
        (the embedding table is gathered row by row, counted per row)."""
        n = self.layers * (self.layer_matmul_params + self.layer_other_params)
        return BYTES * (n + self.d + self.head_params)

    @property
    def kv_bytes_per_position(self) -> int:
        """K and V of one position over all layers."""
        return BYTES * self.layers * 2 * self.kv_heads * self.head_dim

    # -- per-token FLOPs ---------------------------------------------------
    def attn_flops(self, ctx: int) -> int:
        """Scores and weighted sum of one query over ``ctx`` keys, all
        layers."""
        return self.layers * 4 * self.heads * self.head_dim * ctx

    @property
    def token_matmul_flops(self) -> int:
        return 2 * self.layers * self.layer_matmul_params

    @property
    def head_flops(self) -> int:
        return 2 * self.head_params

    # -- calls -------------------------------------------------------------
    def prefill_call(self, rows: int, prompt_len: int) -> "Work":
        """One prefill call of ``rows`` prompts of ``prompt_len`` tokens."""
        S = prompt_len
        causal_pairs = S * (S + 1) // 2
        flops = rows * (S * self.token_matmul_flops
                        + self.layers * 4 * self.heads * self.head_dim
                        * causal_pairs
                        + self.head_flops)
        nbytes = (self.weight_bytes_per_call
                  + rows * S * self.kv_bytes_per_position   # K/V written
                  + rows * S * self.d * BYTES               # embedding rows
                  + rows * self.vocab * BYTES)              # last logits
        return Work(flops=flops, bytes=nbytes, calls=1)

    def decode_call(self, contexts: Sequence[int]) -> "Work":
        """One decode call; ``contexts`` holds, for each live row, the
        number of positions its new token attends to (its own included).
        Done rows are not listed: they count nothing."""
        if not contexts:
            return Work()
        flops = sum(self.token_matmul_flops + self.head_flops
                    + self.attn_flops(c) for c in contexts)
        nbytes = self.weight_bytes_per_call + sum(
            (c - 1) * self.kv_bytes_per_position    # live K/V read
            + self.kv_bytes_per_position            # the new position written
            + self.d * BYTES + self.vocab * BYTES   # embedding row, logits
            for c in contexts)
        return Work(flops=flops, bytes=nbytes, calls=1)

    def decode_rows(self, contexts: Iterable[int]) -> "Work":
        """The per-row part of decode work, with no call's weight read:
        used where rows are known but their grouping into calls is not."""
        w = self.decode_call(list(contexts))
        if w.calls:
            w.bytes -= self.weight_bytes_per_call
            w.calls = 0
        return w


@dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0
    calls: int = 0

    def add(self, other: "Work") -> None:
        self.flops += other.flops
        self.bytes += other.bytes
        self.calls += other.calls

    def least_seconds(self, peak_flops: float, peak_bw: float) -> float:
        return max(self.flops / peak_flops, self.bytes / peak_bw)


def decode_bandwidth_bound(s: Shape, max_rows: int, max_ctx: int,
                           peak_flops: float, peak_bw: float) -> bool:
    """True when even the largest decode call is bound by bytes: then the
    sum of per-call least times equals the larger of the summed bounds."""
    w = s.decode_call([max_ctx] * max_rows)
    return w.flops / peak_flops <= w.bytes / peak_bw
