"""Seconds from the operating system's creation of the server's process to its
bound socket: `process.start` of the `startup` trace (interpreter and imports,
the chips, the compile cache, the backend, the mesh, the server's constructor
and listener)."""

import setup_spans


def read(material):
    return setup_spans.read(material, "server_start_s")
