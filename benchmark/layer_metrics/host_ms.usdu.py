"""Median, over the window's requests, of execute_prompt less the
device.wait spans below it: what the one executor thread spent not
parked waiting for the device."""

import spans


def read(material):
    return spans.median_ms(material, spans.host_seconds)
