"""Over the window's requests, 100 x the summed `starved_s` of the jobs'
records / the summed `device_s` + `starved_s`: of the time the chip
either ran a job's program or had nothing until that job's next launch,
the share it had nothing. From the program's own `device.run` spans
(`idle_before_s`), every request of the window and no profiler: what
`device_idle_in_pct.*` reads off a traced slice's "XLA Modules" line.
Left out where `execute_prompt` bears no record."""

import job_record


def read(material):
    return job_record.starved_pct(material)
