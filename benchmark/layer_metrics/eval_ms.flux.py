"""Median, over the window's requests, of the executor's device wait
(the `device.wait` spans below `execute_prompt`) divided by the model
evaluations the request ran (`evals` on its `node.KSampler` span): what
one evaluation of the denoiser costs the request, the autoencoder's
decode spread over them. Left out where the program sets no `evals`."""

import flux_reduce
import spans


def read(material):
    def one(request):
        evals = flux_reduce.evals_of(request)
        root = spans.seconds(request, "execute_prompt")
        host = spans.host_seconds(request)
        if not evals or root is None or host is None:
            return None
        return (root - host) / float(evals)

    return spans.median_ms(material, one)
