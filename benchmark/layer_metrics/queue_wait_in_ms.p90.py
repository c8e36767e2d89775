"""90th percentile (nearest rank) of what a request waited inside the
program before the executor took it: its sched.wait and
prompt_queue.wait spans. Left out where the program has no
prompt_queue.wait span."""

import spans


def read(material):
    def waited(request):
        if spans.seconds(request, "prompt_queue.wait") is None:
            return None
        return spans.seconds(request, "sched.wait", "prompt_queue.wait")

    return spans.percentile_ms(material, waited, 90)
