"""Median, over the traced slice, of the time on the device's "XLA
Modules" line from the end of one job's tile program
(`jit_upscale_single`) to the start of the next job's, in ms: how long
the device had nothing of either job but the one-operation programs
between (the canvas's cast, the resize, the seed's key), while the
executor thread read the canvas back, handed the save off, came back to
the queue, loaded the next image and walked the graph to its launch. A
slice holds one such gap, or two. Left out where the trace has no such
pair."""

import device_modules

MODULE = "jit_upscale_single"


def read(material):
    return device_modules.gap_after_ms(material, MODULE, (MODULE,))
