"""Operations and bytes the algorithms need, from shapes alone.

Every count here is the LEAST any implementation must do for the work the
window completed, so a share of a roofline or of the chip's peak built on it
cannot pass 100% unless the time leaves work out.  Recomputed, padded or
masked-off work is never counted.  All functions take plain ints.
"""
from __future__ import annotations

BF16 = 2        # bytes: both configurations serve and train in bfloat16


def layer_matmul_params(hidden: int, ffn: int) -> int:
    """Weights of one transformer layer that a token is multiplied by:
    QKV (3h*h), attention output (h*h), two MLP matrices (2*h*ffn)."""
    return 4 * hidden * hidden + 2 * hidden * ffn


def train_flops_per_token(*, hidden: int, ffn: int, layers: int, seq: int,
                          vocab: int, head_share: float) -> float:
    """Forward + backward FLOPs one trained token needs (no recompute).

    6 per matmul weight (2 forward, 4 backward), bidirectional attention
    scores and values at 2*2*seq*hidden forward (x3 with backward), and the
    MLM head (transform h*h + tied vocabulary projection h*vocab) only at
    the ``head_share`` of positions that carry a label."""
    body = 6 * layers * layer_matmul_params(hidden, ffn)
    attention = 3 * layers * 4 * seq * hidden
    head = 6 * head_share * (hidden * hidden + hidden * vocab)
    return float(body + attention + head)


def prefill_flops(n: int, *, hidden: int, ffn: int, layers: int,
                  vocab: int) -> float:
    """FLOPs to prefill a prompt of ``n`` tokens: 2 per matmul weight per
    token, causal attention over the keys each token may see, and one
    vocabulary projection (only the last position's logits are needed)."""
    keys_seen = n * (n + 1) // 2
    return float(2 * n * layers * layer_matmul_params(hidden, ffn)
                 + 4 * hidden * layers * keys_seen + 2 * hidden * vocab)


def decode_flops(context: int, *, hidden: int, ffn: int, layers: int,
                 vocab: int) -> float:
    """FLOPs for one generated token attending ``context`` cached keys."""
    return float(2 * layers * layer_matmul_params(hidden, ffn)
                 + 4 * hidden * layers * context + 2 * hidden * vocab)


def weight_stream_bytes(*, hidden: int, ffn: int, layers: int,
                        vocab: int) -> float:
    """Bytes of bf16 weights a forward pass must read once: every layer
    matrix and the tied vocabulary matrix."""
    return float(BF16 * (layers * layer_matmul_params(hidden, ffn)
                         + hidden * vocab))


def kv_bytes_per_token(*, hidden: int, layers: int) -> int:
    """Bytes of bf16 keys and values one cached token holds over all
    layers."""
    return 2 * layers * hidden * BF16


def prefill_bytes(n: int, *, hidden: int, ffn: int, layers: int,
                  vocab: int) -> float:
    """Least bytes one prefill moves: the weights once, the prompt's keys
    and values written."""
    return (weight_stream_bytes(hidden=hidden, ffn=ffn, layers=layers,
                                vocab=vocab)
            + n * kv_bytes_per_token(hidden=hidden, layers=layers))


def lamb_update_bytes(n_params: int) -> float:
    """Least bytes one LAMB update streams per parameter: fp32 master,
    first and second moment each read and written (3 * 8), the bf16
    gradient read (2) and the bf16 parameter written (2)."""
    return 28.0 * n_params


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
