"""Launch of the worker to ``peer-started`` on its stderr: interpreter,
imports, the launcher, ``kf.init()`` and the backend's bring-up."""


def read(facts, entry):
    launch = facts["launch"]
    if launch["t_peer_started"] is None:
        return None
    return launch["t_peer_started"] - launch["t_launch"]
