"""Median execute_prompt span of the window's requests."""

import reduce


def read(material):
    return reduce.execute_ms(material)
