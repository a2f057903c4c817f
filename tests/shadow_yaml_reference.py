"""The reference for `shadow.yaml`: the whole-document `yaml.dump` of
`Topology.shadow_config()` that wrote the file until the entry layer took to
handing PyYAML a document of constant size and joining the other hosts'
alias lines as text (config/topology.Topology.write_shadow_yaml). It makes
one YAML node a peer, and is kept here, word for word, so that the tests can
hold the writer to its bytes."""

from dst_libp2p_test_node_tpu.config.topology import YAML_FILE, Topology


def write_shadow_yaml(self: Topology, path: str = YAML_FILE) -> None:
    import yaml

    with open(path, "w") as f:
        yaml.dump(self.shadow_config(), f, default_flow_style=False, sort_keys=False)
