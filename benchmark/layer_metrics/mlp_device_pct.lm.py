"""Of the device time the language model's two programs (`jit_prefill`,
`jit_decode`) took in the traced slice, the share, in per cent, of the
operations that the program put under its `mlp` scope (a layer's dense
feed-forward sublayer whole: its norm, the gate and up projection, the
SiLU product, the down projection and the scaled residual sum;
granite-4.0-h-micro has one in each of its 40 layers, two thirds of a
prefill's operations). With `ssm_device_pct.lm` and `attn_device_pct.lm`
it splits that model's device time among its three parts. Self time, read
by `scoped_self_time.py`.

Left out where there is no trace, no such program in it, or where no
operation of those programs names the scope (a program without it)."""

import scoped_self_time

PROGRAMS = ("jit_prefill", "jit_decode")
SCOPE = "mlp"


def read(material):
    return scoped_self_time.share_pct(material, PROGRAMS, SCOPE)
