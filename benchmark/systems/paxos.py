"""Adapter of the Paxos configurations: the program's model. They track
no paths, so no state is converted to the plain reference's form."""


def model(params):
    from stateright_tpu.models.paxos import PaxosModelCfg, paxos_model

    return paxos_model(PaxosModelCfg(
        client_count=params["client_count"],
        server_count=params["server_count"],
        put_count=params.get("put_count", 1)))
