"""Seconds from process start to the first timed step: inputs from the
seed, upload, sketch build, warm step and compile-cache loads."""


def read(run):
    return run.setup_s
