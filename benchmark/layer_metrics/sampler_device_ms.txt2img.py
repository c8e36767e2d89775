"""Median, over the traced slice, of the device seconds of the sampler
program: the events of `jit__img2img_jit` (`models/pipeline._img2img_jit`,
what `KSampler` launches for every family) on the device's "XLA Modules"
line, from when the device began one to when it finished it. All of a
request's model evaluations, and nothing of the autoencoder. Left out
where the trace has no such program."""

import device_modules

MODULE = "jit__img2img_jit"


def read(material):
    return device_modules.median_ms(material, MODULE)
