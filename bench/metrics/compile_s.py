"""``revet.compile`` (front end, pass pipeline, place), host clock."""


def read(rec):
    return rec["setup"]["compile_s"]
