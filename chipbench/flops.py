"""Operations an algorithm REQUIRES, from its shapes, and the chip's peaks:
the numerator and denominator of MFU and roofline shares.  A multiply-add is
two operations; a training step is forward + backward = 3 x forward;
recomputation never counts.  A family's own count (``families/<family>.py``,
``train_flops``) is built from these."""
import json
import os


def peaks(device_kind):
    """The chip's published peaks; an unknown device is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in chipbench/peaks.json")
    return table[device_kind]


def conv2d(h, w, cin, cout, k, stride):
    """(flops, out_h, out_w) of a 'same'-padded k x k convolution."""
    oh, ow = -(-h // stride), -(-w // stride)
    return 2 * oh * ow * cin * cout * k * k, oh, ow
