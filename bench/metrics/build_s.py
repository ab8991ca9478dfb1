"""Build seconds: host clock around the program's subgraph build in set-up."""


def read(obs):
    return obs["spans"].get("build_s")
