"""Process start to the window's start: data, compile, warm launches."""


def read(rec):
    return rec["setup"]["setup_s"]
