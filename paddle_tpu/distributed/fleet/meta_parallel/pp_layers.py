"""Pipeline-parallel layer partitioning.

Reference parity: meta_parallel/parallel_layers/pp_layers.py in
/root/reference (LayerDesc:57, SharedLayerDesc:77, PipelineLayer:209 with
uniform/by-size segmentation).

TPU-native note: the transport between stages is not NCCL p2p but
`lax.ppermute` over the 'pp' mesh axis inside ONE compiled program (see
paddle_tpu.parallel.pipeline for the scan-based GPipe schedule over stacked
stage weights). PipelineLayer here provides the partitioning/bookkeeping
surface; executed on a single process it runs all stages (degree-1
semantics).
"""
from __future__ import annotations

import math

from ....nn.layer import Layer
from ....nn.container import LayerList


class LayerDesc:
    def __init__(self, layer_cls, *inputs, **kwargs):
        self.layer_cls = layer_cls
        self.inputs = inputs
        self.kwargs = kwargs
        if not issubclass(layer_cls, Layer):
            raise TypeError("layer_cls must be a Layer subclass")

    def build_layer(self):
        return self.layer_cls(*self.inputs, **self.kwargs)


class SharedLayerDesc(LayerDesc):
    """Tied-weight stages (e.g. embedding/unembedding, reference :77)."""

    def __init__(self, key, layer_cls, forward_func=None, shared_weight_attr="weight", *inputs, **kwargs):
        super().__init__(layer_cls, *inputs, **kwargs)
        self.layer_name = key
        self.forward_func = forward_func
        self.shared_weight_attr = shared_weight_attr


class PipelineLayer(Layer):
    def __init__(self, layers, num_stages=None, topology=None, loss_fn=None, seg_method="uniform", recompute_interval=0, recompute_ctx=None, num_virtual_pipeline_stages=None, seg_sample_input=None, seg_device_kind=None):
        super().__init__()
        self._loss_fn = loss_fn
        self._topo = topology
        self.num_stages = num_stages or 1
        self._layer_descs = list(layers)
        self.shared_layers = {}

        # figure out this process's stage; single-process SPMD builds all
        if topology is not None and hasattr(topology, "get_coord"):
            try:
                import jax

                coord = topology.get_coord(jax.process_index())
                self.stage_id = coord[topology.get_hybrid_group_names().index("pipe")]
            except Exception:
                self.stage_id = 0
        else:
            self.stage_id = 0

        self.run_all = True  # single-process: run every stage
        built = []
        for i, desc in enumerate(self._layer_descs):
            layer = self._build_one(desc)
            built.append(layer)
        self.run_function = LayerList([l for l in built if isinstance(l, Layer)])
        self._funcs = built
        self.seg_cost_us = None
        if seg_method == "cost":
            # measured-cost balancing (cost_model over XLA's compile-time
            # analysis) instead of uniform layer counts
            if seg_sample_input is None:
                raise ValueError(
                    "seg_method='cost' needs seg_sample_input=<example batch> "
                    "to measure per-layer cost (XLA cost analysis)"
                )
            from ....cost_model import segment_layers_by_cost

            # seg_device_kind names the chip whose roofline prices the
            # layers (None = the one this process runs on; a kind the
            # peaks table does not list raises)
            self.segment_parts, self.seg_cost_us = segment_layers_by_cost(
                self._funcs, self.num_stages, seg_sample_input,
                device_kind=seg_device_kind,
            )
        else:
            self.segment_parts = self._segment(seg_method)

    def _build_one(self, desc):
        if isinstance(desc, SharedLayerDesc):
            if desc.layer_name not in self.shared_layers:
                self.shared_layers[desc.layer_name] = desc.build_layer()
            base = self.shared_layers[desc.layer_name]
            if desc.forward_func is None:
                return base
            fwd = desc.forward_func

            class _SharedCall(Layer):
                def __init__(self, inner):
                    super().__init__()
                    self.inner = inner

                def forward(self, x):
                    return fwd(self.inner, x)

            return _SharedCall(base)
        if isinstance(desc, LayerDesc):
            return desc.build_layer()
        return desc  # already a Layer or a plain callable

    def _segment(self, method):
        n = len(self._layer_descs)
        k = self.num_stages
        if method == "uniform" or not method.startswith("layer:"):
            per = int(math.ceil(n / k))
            parts = [min(i * per, n) for i in range(k)] + [n]
        else:
            # "layer:TransformerBlock" — split evenly by matching class name
            name = method.split(":", 1)[1]
            idxs = [
                i for i, d in enumerate(self._layer_descs)
                if getattr(getattr(d, "layer_cls", type(d)), "__name__", "") == name
            ]
            per = int(math.ceil(len(idxs) / k))
            bounds = [idxs[min(i * per, len(idxs) - 1)] for i in range(k)]
            parts = [0] + bounds[1:] + [n]
        return parts

    def get_stage_from_index(self, idx):
        for stage in range(self.num_stages):
            if self.segment_parts[stage] <= idx < self.segment_parts[stage + 1]:
                return stage
        return self.num_stages - 1

    def forward(self, x):
        for fn in self._funcs:
            x = fn(x)
        return x

    def loss(self, output, label):
        return self._loss_fn(output, label) if self._loss_fn else output
