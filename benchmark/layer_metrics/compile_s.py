"""Compile: seconds in backend compiles (cache loads included) from process
start to the window's opening."""


def read(obs):
    return obs.spans.get("compile_s")
