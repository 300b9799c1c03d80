"""Host seconds of CouplingStore.build in set-up, until its arrays are on
the device."""


def read(run):
    return run.store_build_s
