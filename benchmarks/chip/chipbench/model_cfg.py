"""A configuration file (the published ``config.json`` keys, as run) read
into the few sizes the yardstick needs."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    """Sizes of a dense decoder-only transformer as the benchmark runs
    it: RMSNorm, rotary positions over the whole head, optional q/k/v
    bias, SwiGLU, untied head."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    rope_theta: float
    norm_eps: float
    param_dtype: str

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        if float(c.get("partial_rotary_factor", 1.0)) != 1.0:
            raise ValueError("the program rotates the whole head: "
                             "partial_rotary_factor must be 1.0 as run")
        if c.get("tie_word_embeddings", False):
            raise ValueError("tied embeddings are not run here")
        heads = int(c["num_attention_heads"])
        return cls(
            n_layers=int(c["num_hidden_layers"]),
            d_model=int(c["hidden_size"]),
            n_heads=heads,
            n_kv_heads=int(c.get("num_key_value_heads", heads)),
            head_dim=int(c.get("head_dim",
                               int(c["hidden_size"]) // heads)),
            d_ff=int(c["intermediate_size"]),
            vocab=int(c["vocab_size"]),
            qkv_bias=bool(c.get("use_qkv_bias", c.get("qkv_bias", False))),
            rope_theta=float(c.get("rope_theta", 10000.0)),
            norm_eps=float(c.get("rms_norm_eps",
                                 c.get("layer_norm_eps", 1e-5))),
            param_dtype=str(c.get("param_dtype", "bfloat16")))

    # parameter counts -----------------------------------------------------

    def layer_matmul_params(self) -> int:
        """Weights of one layer that enter a matrix multiplication."""
        d, dh = self.d_model, self.head_dim
        attn = d * dh * (2 * self.n_heads + 2 * self.n_kv_heads)
        return attn + 3 * d * self.d_ff

    def matmul_params(self) -> int:
        """Every layer's matmul weights plus the output head; the
        embedding lookup is a gather and is left out."""
        return (self.n_layers * self.layer_matmul_params()
                + self.d_model * self.vocab)
