"""Operations and bytes the served work needs, computed from shapes.

These count what the model requires, not what an implementation
happens to do: padding rows, masked slots and experts a token was not
routed to are not counted.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Shape:
    layers: int
    d: int
    hq: int
    hkv: int
    hd: int
    ff: int
    vocab: int
    experts: int
    top_k: int
    kv_bytes: int = 2           # bfloat16 KV pool

    @classmethod
    def of(cls, cj: dict) -> "Shape":
        d, h = cj["hidden_size"], cj["num_attention_heads"]
        return cls(layers=cj["num_hidden_layers"], d=d, hq=h,
                   hkv=cj["num_key_value_heads"],
                   hd=cj.get("head_dim") or d // h,
                   ff=cj["intermediate_size"], vocab=cj["vocab_size"],
                   experts=cj.get("num_local_experts", 0),
                   top_k=cj.get("num_experts_per_tok", 0))

    def layer_params(self) -> int:
        """Weights one token's path multiplies by in one layer: q, k, v, o,
        then the MLP, or the router and the top-k experts."""
        attn = self.d * self.hd * (2 * self.hq + 2 * self.hkv)
        if self.experts:
            return attn + self.d * self.experts + self.top_k * 3 * self.d * self.ff
        return attn + 3 * self.d * self.ff

    def attn_flops(self, keys: int) -> int:
        """Scores and weighted values of one query over `keys` keys, one
        layer."""
        return 4 * self.hq * self.hd * keys

    def prefill_flops(self, prompt: int) -> int:
        """One request's prefill: every prompt token through every layer,
        causal attention, and the logits of its last position."""
        causal_keys = prompt * (prompt + 1) // 2
        return (self.layers * (2 * prompt * self.layer_params()
                               + self.attn_flops(causal_keys))
                + 2 * self.d * self.vocab)

    def decode_flops(self, keys: int) -> int:
        """One decoded token attending over `keys` keys."""
        return (self.layers * (2 * self.layer_params() + self.attn_flops(keys))
                + 2 * self.d * self.vocab)

    def paged_attn_bytes(self, keys: int) -> int:
        """HBM bytes one decode row's paged attention needs in one layer:
        K and V of `keys` tokens, the query and the output."""
        kv = 2 * keys * self.hkv * self.hd * self.kv_bytes
        return kv + 2 * self.hq * self.hd * 2

    def decode_attn_bytes(self, keys: int) -> int:
        """`paged_attn_bytes` over every layer."""
        return self.layers * self.paged_attn_bytes(keys)

    def decode_attn_flops(self, keys: int) -> int:
        """`attn_flops` of one decoded token over every layer."""
        return self.layers * self.attn_flops(keys)
