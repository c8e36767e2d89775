"""Seconds from `process.start`'s start to the end of the last set-up request's
`execute_prompt`, less the six other set-up metrics: the device's time in the
first requests, host work in nodes, the client's polling. The share of set-up
no span names; the seven add up to the stretch by construction."""

import setup_spans


def read(material):
    return setup_spans.read(material, "setup_other_s")
