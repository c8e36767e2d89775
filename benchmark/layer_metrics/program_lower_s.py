"""Seconds of lowering to MLIR up to the end of set-up: `lower_s` summed over the
`program.build` spans of the `startup` trace and of set-up's requests."""

import setup_spans


def read(material):
    return setup_spans.read(material, "program_lower_s")
