"""Seconds of jaxpr tracing up to the end of set-up: `trace_s` summed over the
`program.build` spans of the `startup` trace and of set-up's requests, wall
clock, a jit inside a jit counted once."""

import setup_spans


def read(material):
    return setup_spans.read(material, "program_trace_s")
