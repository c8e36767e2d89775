"""Share of the traced slice in which no operation ran on the device."""

import reduce


def read(material):
    return reduce.device_idle_pct(material)
