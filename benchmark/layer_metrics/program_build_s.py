"""Seconds the backend spent compiling up to the end of set-up, retrieval left
out: `build_s` summed over the `program.build` spans of the `startup` trace and
of set-up's requests. Near 0 on a cached start; what it is not names, by the
spans' `program`, what was rebuilt."""

import setup_spans


def read(material):
    return setup_spans.read(material, "program_build_s")
