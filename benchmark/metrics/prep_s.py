"""prep_s: host seconds to build the program's operator from the
configuration and place it on the chips (partition and assembly on the
distributed path), compilation excluded."""


def read(run):
    return run.prep_s
