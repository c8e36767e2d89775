"""90th percentile (nearest rank), over the window's requests, of
`waiting_s` of the job's record: what a request of the burst spent
neither on the chip, nor starving it, nor in its save: admitted late,
queued, and launched behind the jobs before it. Left out where
`execute_prompt` bears no record."""

import job_record
import spans


def read(material):
    return spans.percentile_ms(material, job_record.part_of("waiting_s"), 90)
