"""Seconds spent fetching built programs from the compile cache up to the end of
set-up: `fetch_s` summed over the `program.build` spans of the `startup` trace
and of set-up's requests."""

import setup_spans


def read(material):
    return setup_spans.read(material, "program_fetch_s")
