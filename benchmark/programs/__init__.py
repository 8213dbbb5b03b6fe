"""What the benchmark takes from the program: one file per model family
that builds the network, and :func:`set_weights`."""
import importlib


def program(cfg):
    return importlib.import_module("benchmark.programs." + cfg["family"])


def set_weights(net, specs, arrays):
    """Put the benchmark's weights (flat, in the reference's order, which
    is the order Gluon registers the parameters in) into the network,
    checking each against the parameter it lands in."""
    from mxnet_tpu.ndarray import NDArray

    params = list(net.collect_params().values())
    if len(params) != len(specs):
        raise SystemExit("the program has %d parameters, the reference %d"
                         % (len(params), len(specs)))
    for p, (name, shape, _kind), arr in zip(params, specs, arrays):
        tail = name.rsplit("_", 1)[-1]
        known = all(a in (0, b) for a, b in zip(p.shape, shape))
        if not p.name.endswith(tail) or len(p.shape) != len(shape) \
                or not known:
            raise SystemExit("parameter %s %s does not take %s %s"
                             % (p.name, p.shape, name, shape))
        p.set_data(NDArray(arr))
    return params
