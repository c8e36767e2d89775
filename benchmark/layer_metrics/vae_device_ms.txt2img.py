"""Median, over the traced slice, of the device seconds of the
autoencoder's program: the events of `jit_vae_apply` (`ops/tiled_vae.
vae_apply`, one jitted pass; a txt2img job's one is its decode) on the
device's "XLA Modules" line. Left out where the trace has no such
program."""

import device_modules

MODULE = "jit_vae_apply"


def read(material):
    return device_modules.median_ms(material, MODULE)
