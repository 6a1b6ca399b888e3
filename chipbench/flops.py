"""Model FLOPs per trained token, from a configuration's sizes.

Counts the operations the forward and backward passes require (backward =
twice forward), two per multiply-add, with nothing recomputed:

* every weight matrix, the tied embedding head included (the embedding
  lookup is free);
* attention scores and the weighted sum of values over the whole sequence,
  as in PaLM's model-FLOPs convention (the causal half is not subtracted);
* the chunked SSD algorithm's terms at the configuration's chunk size:
  C.B within a chunk, the intra-chunk output, each chunk's state and the
  inter-chunk output, plus the depthwise convolution.

Elementwise work (norms, activations, softmax, decays) is not counted.
"""
from __future__ import annotations


def _dense_layer_macs(m: dict, seq: int) -> int:
    d, dh = m["d_model"], m["head_dim"]
    hq, hk = m["num_heads"] * dh, m["num_kv_heads"] * dh
    proj = d * hq + 2 * d * hk + hq * d + 3 * d * m["d_ff"]
    attn = 2 * seq * hq            # q.k over the sequence, then probs.v
    return proj + attn


def _ssd_layer_macs(m: dict, seq: int) -> int:
    d, ns, ph, q = (m["d_model"], m["ssm_state"], m["ssm_head_dim"],
                    m["ssm_chunk"])
    di = m["ssm_expand"] * d
    nh = di // ph
    proj = d * (2 * di + 2 * ns + nh) + di * d
    conv = m["ssm_conv_width"] * (di + 2 * ns)
    chunk = q * ns + q * di + 2 * di * ns   # C.B, intra output, state, inter
    return proj + conv + chunk


def flops_per_token(m: dict, seq: int) -> float:
    """Training FLOPs per token of model sizes ``m`` at sequence ``seq``."""
    layer = {"global": _dense_layer_macs,
             "ssd": _ssd_layer_macs}[m["block_pattern"][0]]
    macs = m["num_layers"] * layer(m, seq) + m["d_model"] * m["vocab_size"]
    return 3 * 2 * float(macs)
