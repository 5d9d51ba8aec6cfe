"""Events committed by every lane in the measured window, per second of it."""


def read(window):
    return window["events"] / window["wall_s"]
