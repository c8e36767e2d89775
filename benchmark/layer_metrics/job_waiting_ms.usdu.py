"""Median, over the window's requests, of `waiting_s` of the job's
record: from the request's arrival to the end of `execute_prompt`, the
time in which neither one of its own programs was on the chip nor the
chip sat idle for want of its next launch, and its last program had not
ended: queued, walked or launched while the chip did earlier jobs' work.
Left out where `execute_prompt` bears no record."""

import job_record
import spans


def read(material):
    return spans.median_ms(material, job_record.part_of("waiting_s"))
