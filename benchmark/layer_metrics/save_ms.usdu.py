"""Median, over the window's requests, of the png.encode and file.write
spans of one request: the save that follows the read-back."""

import spans


def read(material):
    return spans.median_ms(
        material, lambda request: spans.seconds(request, "png.encode", "file.write")
    )
