"""Operations and bytes the algorithm needs, from shapes alone.

Whatever implements a layer, these count only what the mathematics asks
for: two operations per multiply-add, every weight read once, keys and
values of live tokens only. Padding, recomputation and wasted rows are not
counted, so a share taken of them cannot pass 100 %.
"""

from __future__ import annotations

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def dtype_bytes(name: str) -> int:
    return _DTYPE_BYTES[name]


# ------------------------------------------------------------- decoder LM --
def gpt_param_count(vocab, hidden, layers, positions, inter=None,
                    qkv_bias=False):
    """Parameters of a GPT-2 style decoder with a tied head."""
    inter = inter or 4 * hidden
    attn = 4 * hidden * hidden + (4 * hidden if qkv_bias else 0)
    mlp = 2 * hidden * inter + inter + hidden
    norms = 4 * hidden
    return (vocab * hidden + positions * hidden + 2 * hidden
            + layers * (attn + mlp + norms))


def gpt_token_flops(context, hidden, layers, inter=None):
    """Forward operations of ONE token whose attention spans ``context``
    positions (itself included), LM head excluded: QKV+O 8h^2, MLP
    4*h*inter, scores and mix 4*context*h, per layer."""
    inter = inter or 4 * hidden
    return layers * (8 * hidden * hidden + 4 * hidden * inter
                     + 4 * context * hidden)


def gpt_head_flops(vocab, hidden):
    return 2 * hidden * vocab


def gpt_prefill_flops(prompt_len, vocab, hidden, layers, inter=None):
    """A causal prompt pass at its true length: token i attends to i
    positions; one row of logits (the last position's)."""
    inter = inter or 4 * hidden
    dense = layers * (8 * hidden * hidden + 4 * hidden * inter)
    attn = layers * 4 * hidden * (prompt_len * (prompt_len + 1) // 2)
    return prompt_len * dense + attn + gpt_head_flops(vocab, hidden)


def gpt_decode_flops(context, vocab, hidden, layers, inter=None):
    """One generated token fed back at ``context`` positions, with its
    row of logits."""
    return (gpt_token_flops(context, hidden, layers, inter)
            + gpt_head_flops(vocab, hidden))


def gpt_decode_step_need(live_slots, live_tokens, vocab, hidden, layers,
                         positions, weight_bytes, cache_bytes,
                         logit_bytes=4, inter=None):
    """What one decode step over ``live_slots`` streams holding
    ``live_tokens`` cached positions between them has to do: returns
    ``(flops, bytes)``. Every weight once; K and V of the live tokens
    only, plus the new row written; one row of logits per live slot."""
    n_param = gpt_param_count(vocab, hidden, layers, positions, inter)
    kv = 2 * layers * hidden * cache_bytes          # K and V of one token
    bytes_ = (n_param * weight_bytes + live_tokens * kv + live_slots * kv
              + live_slots * vocab * logit_bytes)
    inter = inter or 4 * hidden
    flops = (live_slots * (layers * (8 * hidden * hidden + 4 * hidden * inter)
                           + gpt_head_flops(vocab, hidden))
             + layers * 4 * hidden * live_tokens)
    return flops, bytes_


# ----------------------------------------------------------------- ResNet --
_BOTTLENECK_STAGES = {50: (3, 4, 6, 3)}


def resnet_layers(depth=50, image=224, classes=1000):
    """Every convolution and the classifier of a bottleneck ResNet
    (He et al., table 1; stride on the 3x3, projection shortcuts where
    the shape changes): ``(name, out_h, out_w, k, c_in, c_out)``."""
    out = []
    hw = (image + 2 * 3 - 7) // 2 + 1
    out.append(("conv1", hw, hw, 7, 3, 64))
    hw = (hw + 2 * 1 - 3) // 2 + 1                      # 3x3/2 max pool
    c_in = 64
    for si, (blocks, planes) in enumerate(zip(_BOTTLENECK_STAGES[depth],
                                              (64, 128, 256, 512))):
        for bi in range(blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            name = f"res{si + 2}_{bi}"
            hw_out = hw // stride
            if c_in != planes * 4 or stride != 1:
                out.append((name + "_proj", hw_out, hw_out, 1, c_in,
                            planes * 4))
            out.append((name + "_conv1", hw, hw, 1, c_in, planes))
            out.append((name + "_conv2", hw_out, hw_out, 3, planes, planes))
            out.append((name + "_conv3", hw_out, hw_out, 1, planes,
                        planes * 4))
            hw, c_in = hw_out, planes * 4
    out.append(("fc", 1, 1, 1, c_in, classes))
    return out


def resnet_forward_macs(depth=50, image=224, classes=1000):
    """Multiply-adds of one image's forward pass, convolutions and the
    classifier (batch norm, ReLU and pooling are not matrix work)."""
    return sum(h * w * k * k * ci * co
               for _, h, w, k, ci, co in resnet_layers(depth, image, classes))


def resnet_param_count(depth=50, classes=1000):
    n = 0
    for name, _, _, k, ci, co in resnet_layers(depth, 224, classes):
        n += k * k * ci * co
        n += co if name == "fc" else 2 * co             # fc bias; BN pair
    return n


def resnet_train_step_flops(batch, depth=50, image=224, classes=1000):
    """Forward and backward of one step: two operations per multiply-add,
    the backward pass twice the forward (one product for the inputs'
    gradient, one for the weights')."""
    return 3 * 2 * resnet_forward_macs(depth, image, classes) * batch
