"""Process start to the start of the measured window, in seconds."""


def read(window):
    return window["setup_seconds"]
