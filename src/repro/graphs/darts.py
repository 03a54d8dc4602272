"""DARTS (Liu et al., ICLR 2019) as SERENITY graphs: the learned normal
cell, and the published ImageNet network built from it.

Published DARTS_V2 genotype, normal cell:

    normal = [(sep_conv_3x3, 0), (sep_conv_3x3, 1),
              (sep_conv_3x3, 0), (sep_conv_3x3, 1),
              (sep_conv_3x3, 1), (skip_connect, 0),
              (skip_connect, 0), (dil_conv_3x3, 2)]
    concat = [2, 3, 4, 5]

Each intermediate node sums two operand branches; a sep_conv is the standard
ReLU-Conv(dw)-Conv(1x1)-BN stack applied twice; dil_conv applies it once.  The
paper evaluates the *first* normal cell of the ImageNet network (highest
footprint): feature maps 28x28, C=48 channels after the stem, float32.

``darts_normal_cell``      — the single-cell benchmark above.
``darts_imagenet_network`` — the published DARTS_V2 ImageNet network
(``NetworkImageNet``: stems, 14 normal and reduction cells, classifier).
``darts_network``          — a stand-in of ``FULL_NETWORKS`` and the CPU
scheduling benches: one discovered normal cell repeated ``n_cells`` times.
``double_skip=False`` (default) wires each cell off its predecessor's
output only, the hourglass chain the hierarchical scheduler
decomposes exactly; ``double_skip=True`` adds the genotype's ``c_{k-2}``
skip, which keeps *two* tensors live across every cell boundary — no
single-node separator exists and the search has to rely on pruning alone
(the stress case for branch and bound).
"""

from __future__ import annotations

from repro.core.graph import Graph

# (op, input_index) pairs per intermediate node; indices 0,1 are the two cell
# inputs, 2.. are previous intermediate nodes.
DARTS_V2_NORMAL = [
    [("sep_conv_3x3", 0), ("sep_conv_3x3", 1)],   # node 2
    [("sep_conv_3x3", 0), ("sep_conv_3x3", 1)],   # node 3
    [("sep_conv_3x3", 1), ("skip_connect", 0)],   # node 4
    [("skip_connect", 0), ("dil_conv_3x3", 2)],   # node 5
]
CONCAT = [2, 3, 4, 5]
# DARTS_V2 reduction cell.  Ops on the cell inputs (indices 0, 1) take
# stride 2; skip_connect of an intermediate node is the identity.
DARTS_V2_REDUCE = [
    [("max_pool_3x3", 0), ("max_pool_3x3", 1)],   # node 2
    [("skip_connect", 2), ("max_pool_3x3", 1)],   # node 3
    [("max_pool_3x3", 0), ("skip_connect", 2)],   # node 4
    [("skip_connect", 2), ("max_pool_3x3", 1)],   # node 5
]


def _add_cell(
    specs: list[dict],
    in0: int,
    in1: int,
    *,
    fmap: int,
    sep_w: int,
    tag: str = "",
) -> int:
    """Append one DARTS_V2 normal cell reading (in0, in1); returns the
    concat node id (the cell output before the transition conv)."""

    def add(name, op, size, preds=(), weight=0):
        specs.append(
            dict(name=name, op=op, size_bytes=size, preds=list(preds),
                 weight_bytes=weight)
        )
        return len(specs) - 1

    def sep_conv(tag_: str, src: int) -> int:
        # ReLU -> dwconv -> pwconv -> BN, twice (DARTS SepConv definition).
        x = src
        for rep in range(2):
            r = add(f"{tag_}.relu{rep}", "relu", fmap, [x])
            d = add(f"{tag_}.dw{rep}", "depthconv", fmap, [r],
                    weight=sep_w // 2)
            p = add(f"{tag_}.pw{rep}", "conv", fmap, [d], weight=sep_w // 2)
            x = add(f"{tag_}.bn{rep}", "bn", fmap, [p])
        return x

    def dil_conv(tag_: str, src: int) -> int:
        r = add(f"{tag_}.relu", "relu", fmap, [src])
        d = add(f"{tag_}.dw", "depthconv", fmap, [r], weight=sep_w // 2)
        p = add(f"{tag_}.pw", "conv", fmap, [d], weight=sep_w // 2)
        return add(f"{tag_}.bn", "bn", fmap, [p])

    node_out = {0: in0, 1: in1}
    for i, edges in enumerate(DARTS_V2_NORMAL):
        node_id = i + 2
        branch_outs = []
        for j, (op, src_idx) in enumerate(edges):
            src = node_out[src_idx]
            btag = f"{tag}n{node_id}.e{j}.{op}"
            if op == "sep_conv_3x3":
                branch_outs.append(sep_conv(btag, src))
            elif op == "dil_conv_3x3":
                branch_outs.append(dil_conv(btag, src))
            elif op == "skip_connect":
                branch_outs.append(src)
            else:
                raise ValueError(op)
        node_out[node_id] = add(f"{tag}n{node_id}.add", "add", fmap,
                                branch_outs)

    concat_in = [node_out[i] for i in CONCAT]
    return add(f"{tag}cell.concat", "concat", fmap * len(CONCAT), concat_in)


def darts_normal_cell(
    hw: int = 28, channels: int = 48, dtype_bytes: int = 4
) -> Graph:
    fmap = hw * hw * channels * dtype_bytes          # one C-channel feature map
    k = 3
    sep_w = (channels * k * k + channels * channels) * dtype_bytes  # dw + pw
    specs: list[dict] = []
    specs.append(dict(name="c_{k-2}", op="input", size_bytes=fmap, preds=[],
                      weight_bytes=0))
    specs.append(dict(name="c_{k-1}", op="input", size_bytes=fmap, preds=[],
                      weight_bytes=0))
    cc = _add_cell(specs, 0, 1, fmap=fmap, sep_w=sep_w)
    # cells are followed by a 1x1 conv when channels change; model the
    # downstream consumer so concat liveness is realistic:
    specs.append(dict(name="next.pw", op="conv", size_bytes=fmap, preds=[cc],
                      weight_bytes=4 * channels * channels * dtype_bytes))
    return Graph.build(specs, name="darts_imagenet_cell")


def darts_network(
    n_cells: int = 6,
    hw: int = 28,
    channels: int = 48,
    dtype_bytes: int = 4,
    double_skip: bool = False,
) -> Graph:
    """The deployed DARTS form: one normal cell repeated ``n_cells`` times.

    Every cell's concat feeds a 1x1 transition conv whose output is the next
    cell's input, so with ``double_skip=False`` each transition is a
    single-node separator and the partition tree reduces the network to
    ``n_cells`` isomorphic leaves — scheduled once, replayed for the rest.
    ``double_skip=True`` additionally feeds each cell its grandparent's
    transition output (the published genotype's ``c_{k-2}`` input): two
    tensors then stay live across every boundary, no separator exists, and
    the whole network is a single exact-search cell (branch-and-bound
    stress case; expect the soft-budget/beam machinery at realistic sizes).

    ``n_cells=6`` gives a 207-node chain (``double_skip`` adds no nodes,
    only edges).
    """
    fmap = hw * hw * channels * dtype_bytes
    k = 3
    sep_w = (channels * k * k + channels * channels) * dtype_bytes
    specs: list[dict] = []
    specs.append(dict(name="stem", op="input", size_bytes=fmap, preds=[],
                      weight_bytes=0))
    prev_prev = prev = 0
    for ci in range(n_cells):
        in0 = prev_prev if double_skip else prev
        cc = _add_cell(specs, in0, prev, fmap=fmap, sep_w=sep_w,
                       tag=f"c{ci}.")
        specs.append(dict(name=f"c{ci}.trans.pw", op="conv", size_bytes=fmap,
                          preds=[cc],
                          weight_bytes=4 * channels * channels * dtype_bytes))
        prev_prev, prev = prev, len(specs) - 1
    tag = "skip" if double_skip else "chain"
    return Graph.build(specs, name=f"darts_net_x{n_cells}_{tag}")


def darts_imagenet_network(
    layers: int = 14,
    channels: int = 48,
    image_hw: int = 224,
    classes: int = 1000,
) -> Graph:
    """The published DARTS_V2 ImageNet network (``NetworkImageNet``).

    DARTS (arXiv 1806.09055), Table 3, mobile setting: 14 cells, C=48,
    224x224 input, 4.7M parameters.  The layout follows ``NetworkImageNet``
    and ``genotypes.DARTS_V2`` in the authors' code (quark0/darts, cnn/):

      * ``stem0``: conv3x3 s2 to C/2, BN, ReLU, conv3x3 s2 to C, BN;
      * ``stem1``: ReLU, conv3x3 s2, BN;
      * cells at ``layers // 3`` and ``2 * layers // 3`` reduce (stride 2,
        channels doubled); every cell reads ``c_{k-2}`` and ``c_{k-1}``,
        preprocessed to C by ``ReLUConvBN`` 1x1, or by ``FactorizedReduce``
        when the cell before it reduced (the stem counts as one);
      * global average pool, then a linear classifier.

    Node conventions are :func:`darts_normal_cell`'s: a ``SepConv`` is
    twice (``relu``, ``depthconv``, ``conv`` 1x1, ``bn``) with the stride on
    the first depthwise, a ``DilConv`` one such stack, ``ReLUConvBN`` is
    ``relu``, ``conv``, ``bn``, and ``FactorizedReduce`` is ``relu``, two
    strided ``conv``s to C/2, ``concat``, ``bn``.  A max pool is one
    ``pool`` node, skip_connect adds no node.  Every ``conv``,
    ``depthconv`` and the classifier (``fc``) carry their weights in
    ``weight_bytes``; biases and batch-norm parameters are not counted.
    Inference only: the auxiliary head and drop-path are training-time
    parts and are left out.  Batch 1, float32: 4 bytes per element, the
    element size the executor and its reference assume.

    The defaults give 709 nodes and 4,681,416 weight parameters.
    """
    specs: list[dict] = []

    def add(name, op, hw, c, preds=(), weight=0):
        specs.append(dict(name=name, op=op,
                          size_bytes=hw * hw * c * 4,
                          preds=list(preds),
                          weight_bytes=weight * 4))
        return len(specs) - 1

    def relu_conv_bn(tag, src, hw, c_in, c_out):
        r = add(f"{tag}.relu", "relu", hw, c_in, [src])
        k = add(f"{tag}.conv", "conv", hw, c_out, [r], c_in * c_out)
        return add(f"{tag}.bn", "bn", hw, c_out, [k])

    def factorized_reduce(tag, src, hw, c_in, c_out):
        r = add(f"{tag}.relu", "relu", hw, c_in, [src])
        half = [add(f"{tag}.conv{j}", "conv", hw // 2, c_out // 2, [r],
                    c_in * (c_out // 2)) for j in (1, 2)]
        cc = add(f"{tag}.concat", "concat", hw // 2, c_out, half)
        return add(f"{tag}.bn", "bn", hw // 2, c_out, [cc])

    def dw_pw_stacks(tag, src, hw, c, stride, reps):
        x = src
        for rep in range(reps):
            r = add(f"{tag}.relu{rep}", "relu", hw, c, [x])
            if rep == 0:
                hw //= stride              # the first depthwise strides
            d = add(f"{tag}.dw{rep}", "depthconv", hw, c, [r], 9 * c)
            p = add(f"{tag}.pw{rep}", "conv", hw, c, [d], c * c)
            x = add(f"{tag}.bn{rep}", "bn", hw, c, [p])
        return x

    def edge(tag, op, src, hw, c, stride):
        if op == "sep_conv_3x3":
            return dw_pw_stacks(tag, src, hw, c, stride, 2)
        if op == "dil_conv_3x3":
            return dw_pw_stacks(tag, src, hw, c, stride, 1)
        if op == "max_pool_3x3":
            return add(tag, "pool", hw // stride, c, [src])
        if op == "skip_connect" and stride == 1:
            return src
        raise ValueError(f"{op} with stride {stride}")

    c = channels
    x = add("image", "input", image_hw, 3)
    hw = image_hw // 2
    x = add("stem0.conv0", "conv", hw, c // 2, [x], 9 * 3 * (c // 2))
    x = add("stem0.bn0", "bn", hw, c // 2, [x])
    x = add("stem0.relu", "relu", hw, c // 2, [x])
    hw //= 2
    x = add("stem0.conv1", "conv", hw, c, [x], 9 * (c // 2) * c)
    s0 = add("stem0.bn1", "bn", hw, c, [x])
    x = add("stem1.relu", "relu", hw, c, [s0])
    x = add("stem1.conv", "conv", hw // 2, c, [x], 9 * c * c)
    s1 = add("stem1.bn", "bn", hw // 2, c, [x])

    # (tensor, spatial size, channels) of c_{k-2} and c_{k-1}
    prev_prev, prev = (s0, hw, c), (s1, hw // 2, c)
    reduction_prev = True
    for ci in range(layers):
        reduction = ci in (layers // 3, 2 * layers // 3)
        if reduction:
            c *= 2
        tag = f"c{ci}."
        (t0, hw0, c0), (t1, hw1, c1) = prev_prev, prev
        if reduction_prev:
            in0 = factorized_reduce(f"{tag}pre0", t0, hw0, c0, c)
        else:
            in0 = relu_conv_bn(f"{tag}pre0", t0, hw0, c0, c)
        in1 = relu_conv_bn(f"{tag}pre1", t1, hw1, c1, c)
        genotype = DARTS_V2_REDUCE if reduction else DARTS_V2_NORMAL
        out_hw = hw1 // 2 if reduction else hw1
        states = [in0, in1]
        for i, edges in enumerate(genotype):
            branches = []
            for j, (op, src) in enumerate(edges):
                stride = 2 if reduction and src < 2 else 1
                src_hw = hw1 if src < 2 else out_hw
                branches.append(edge(f"{tag}n{i + 2}.e{j}.{op}", op,
                                     states[src], src_hw, c, stride))
            states.append(add(f"{tag}n{i + 2}.add", "add", out_hw, c,
                              branches))
        cc = add(f"{tag}concat", "concat", out_hw, c * len(CONCAT),
                 [states[i] for i in CONCAT])
        prev_prev, prev = prev, (cc, out_hw, c * len(CONCAT))
        reduction_prev = reduction

    t, _, c_last = prev
    pooled = add("head.pool", "pool", 1, c_last, [t])
    add("head.fc", "fc", 1, classes, [pooled], c_last * classes)
    return Graph.build(
        specs, name=f"darts_v2_imagenet_{layers}x{channels}_{image_hw}")
