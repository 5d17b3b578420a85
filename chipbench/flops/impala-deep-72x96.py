"""Operations of the deep IMPALA agent (arXiv:1802.01561 Fig. 3 right)
per observation, counted from its shapes: two per multiply-add of every
conv and dense layer, counting only the kernel taps that land
inside the input (padding does no work). Elementwise work (ReLU,
pooling, the LSTM's gates) is left out; it is about 2% here.

A train step runs the forward pass and the backward pass over each of a
trajectory's T+1 observations; the backward pass costs two forward
passes, less the input gradient of the first conv, which no one needs.
"""
import math

CHANNELS = (16, 32, 32)


def _taps(n, k, stride):
    """Kernel taps that land inside the input, summed over the outputs of
    one axis of a SAME-padded conv: padding contributes no work."""
    out = math.ceil(n / stride)
    lo = max((out - 1) * stride + k - n, 0) // 2
    return sum(min(i * stride - lo + k, n) - max(i * stride - lo, 0)
               for i in range(out))


def _conv(h, w, cin, cout, k=3, stride=1):
    flops = 2 * cin * cout * _taps(h, k, stride) * _taps(w, k, stride)
    return flops, math.ceil(h / stride), math.ceil(w / stride)


def forward_flops(cfg) -> float:
    h, w, c = cfg["frame"]
    a, width, fc = cfg["num_actions"], cfg["lstm_width"], cfg["fc_width"]
    total, cin = 0, c
    for ch in CHANNELS:
        f, h, w = _conv(h, w, cin, ch)
        total += f
        h, w = math.ceil(h / 2), math.ceil(w / 2)       # max-pool 3x3/2
        total += 4 * _conv(h, w, ch, ch)[0]             # two residual blocks
        cin = ch
    total += 2 * h * w * cin * fc                       # FC
    total += 2 * (fc + a + 1 + width) * 4 * width       # LSTM
    total += 2 * width * fc + 2 * fc * (a + 1)          # post-LSTM FC, heads
    return float(total)


def train_flops(cfg) -> float:
    h, w, c = cfg["frame"]
    first_input_grad = _conv(h, w, c, CHANNELS[0])[0]
    return 3 * forward_flops(cfg) - first_input_grad
