"""Peak bytes in use on the fullest device after the window
(cdt_device_memory_bytes{stat="peak_bytes_in_use"}), in 1e9 bytes."""


def read(material):
    peaks = material["after_window"]["peak_bytes_in_use"]
    return max(peaks.values()) / 1e9 if peaks else None
