"""Set-up before the window program is compiled or loaded: imports, the
backend coming up, reference build, engine constructor and initial state
(host clock; the initial state's many small programs compile in here)."""


def read(trace, counters, spans):
    return spans["imports"] + spans["backend"] + spans["build"]
