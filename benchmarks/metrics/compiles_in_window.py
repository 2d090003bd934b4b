"""Compile requests between the window's opening and its close; has to
read 0.  Layer: start-up."""


def read(run, name):
    return run.at_close["compiles"] - run.at_window["compiles"]
