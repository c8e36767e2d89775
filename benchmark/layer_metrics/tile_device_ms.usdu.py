"""Median, over the window's requests, of the device seconds of a job's one
tile program as the program itself saw them: `busy_s` of
`device.run{program=upscale_single}` under
`node.UltimateSDUpscaleDistributed`, from when the launch returned to
when the watcher thread found the canvas ready. A host reading, and the
one new metric that is: the program runs 11 s, a traced slice is 15 s and
holds one whole `jit_upscale_single` in one run of three, so the device's
own line cannot give it. The host learns of the end 1-2 ms late and the
launch returns under a millisecond after the device began (PERF.md §6, PR
36): 0.02 % of the reading. Beside it `execute_ms.usdu` less
`host_ms.usdu` is the same interval as the executor thread waited it out.
Left out where the program opens no `device.run`."""

import device_spans


def read(material):
    return device_spans.busy_ms(material, "upscale_single")
