"""Seconds set-up's requests spent in their loader nodes (`node.*Loader*`:
`CheckpointLoaderSimple`, `UNETLoader`, `CLIPLoader`, `VAELoader`; not
`LoadImage`) less the `program.build` spans under them: weights drawn or read
and placed."""

import setup_spans


def read(material):
    return setup_spans.read(material, "loaders_s")
