"""The warm launches: one DeviceProgram jit and one launch per bucket the
traffic uses, host clock."""


def read(rec):
    return rec["setup"]["warm_s"]
