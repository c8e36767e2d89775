"""90th percentile (nearest rank) of what a request waited outside the
executor: client latency minus its execute_prompt span."""

import reduce


def read(material):
    return reduce.queue_wait_ms(material, 90)
