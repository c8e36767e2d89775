"""Median, over the window's requests, of `tail_s` of the job's record:
from the end of the job's last program on the chip to the end of
`execute_prompt`: the read-back once the image is ready, the PNG encode,
the file write and the hand-off. Left out where `execute_prompt` bears
no record."""

import job_record
import spans


def read(material):
    return spans.median_ms(material, job_record.part_of("tail_s"))
