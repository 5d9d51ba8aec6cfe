"""Peak bytes in use on the fullest chip, as the backend reports it, in MB."""


def read(window):
    return window["peak_bytes"] / 1e6
