"""Set-up time: process start to the opening of the measured window
(JAX start-up, compiles or compile-cache loads, building the service
and filling its live window through the timed path)."""


def read(rec):
    return rec["setup_s"]
