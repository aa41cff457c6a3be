"""The prefix table's lifetime rule (PR 34): an entry lives while a later
pipeline can still ask for it, that is while every object its prefix
names by identity is alive, and goes, with the fitted estimator it
pins, when one of them does."""

import gc

import numpy as np
import pytest

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.workflow.executor import PipelineEnv
from keystone_tpu.workflow.graph import Graph
from keystone_tpu.workflow.operators import DatasetOperator, DatumOperator, Expression, Operator
from keystone_tpu.workflow.pipeline import Estimator, Transformer
from keystone_tpu.workflow.prefix import PrefixTable, find_prefix


class Op(Operator):
    pass


class Doubler(Transformer):
    def __init__(self, payload):
        self.payload = payload  # stands for a fitted model's device arrays

    def apply(self, x):
        return 2 * x


class CountingEstimator(Estimator):
    def __init__(self):
        self.fits = 0

    def fit(self, data):
        self.fits += 1
        return Doubler(np.zeros(4))


def _graph(op, dataset):
    g = Graph()
    g, d = g.add_node(DatasetOperator(dataset), [])
    g, a = g.add_node(op, [d])
    return g, a


def test_an_entry_is_found_by_an_equal_prefix_built_again():
    op, ds = Op(), ArrayDataset(np.ones((4, 2), np.float32))
    table = PrefixTable()
    g1, a1 = _graph(op, ds)
    table[find_prefix(g1, a1)] = Expression.of("kept")
    g2, a2 = _graph(op, ds)  # another graph, another DatasetOperator, the same objects
    again = find_prefix(g2, a2)
    assert again in table and table[again].get() == "kept" and len(table) == 1
    other_data = find_prefix(*_graph(op, ArrayDataset(np.ones((4, 2), np.float32))))
    other_op = find_prefix(*_graph(Op(), ds))
    assert other_data not in table and other_op not in table
    with pytest.raises(KeyError):
        table[other_op]


@pytest.mark.parametrize("which", ["operator", "dataset"])
def test_an_entry_goes_when_an_object_of_its_prefix_does(which):
    op, ds = Op(), ArrayDataset(np.ones((4, 2), np.float32))
    table = PrefixTable()
    g, a = _graph(op, ds)
    table[find_prefix(g, a)] = Expression.of("pinned")
    del g, a
    assert len(table) == 1
    if which == "operator":
        del op
    else:
        del ds
    assert len(table) == 0  # at once: no garbage collection needed


def test_a_datum_that_cannot_be_referenced_weakly_pins_its_entry_as_before():
    g = Graph()
    g, d = g.add_node(DatumOperator(7), [])
    op = Op()
    g, a = g.add_node(op, [d])
    table = PrefixTable()
    prefix = find_prefix(g, a)
    table[prefix] = Expression.of("kept")
    assert prefix in table
    del g, prefix
    gc.collect()
    assert len(table) == 1  # `op` is alive and 7 cannot die
    table.clear()
    assert len(table) == 0


def test_one_pipeline_applied_twice_fits_once_and_the_fit_is_freed_with_the_pipeline():
    PipelineEnv.reset()
    est = CountingEstimator()
    data = ArrayDataset(np.ones((8, 4), np.float32))
    pipeline = est.with_data(data)
    for i in range(3):
        assert len(pipeline.apply(ArrayDataset(np.full((2, 4), float(i), np.float32))).get()) == 2
    assert est.fits == 1
    again = est.with_data(data)  # the same estimator object over the same data
    again.apply(ArrayDataset(np.zeros((2, 4), np.float32))).get()
    assert est.fits == 1 and len(PipelineEnv.get_or_create().state) == 1
    del pipeline, again, est
    gc.collect()  # a lazily applied pipeline's results hold cycles of their own
    assert len(PipelineEnv.get_or_create().state) == 0


def test_a_fitted_pipelines_model_is_freed_when_the_fit_is_dropped():
    """The benchmark's loop: a new Pipeline a fit, the last fit dropped
    before the next. Nothing of a fit stays behind in the table."""
    import weakref

    from keystone_tpu.pipelines import timit

    PipelineEnv.reset()
    gc.collect()
    gc.disable()  # the table must not lean on the collector
    try:
        models = []
        for i in range(3):
            train = timit.synthetic_timit(128, seed=i)
            config = timit.TimitConfig(solver="kernel", kernel_block_size=64, reg=1.0)
            fitted = timit.build_pipeline(config, train).fit()
            mapper = next(
                op for op in fitted.graph.operators.values() if type(op).__name__ == "KernelBlockLinearMapper"
            )
            models.append(weakref.ref(mapper))
            assert len(PipelineEnv.get_or_create().state) == 0  # the Pipeline is gone already
            del fitted, mapper, train
            assert models[-1]() is None, "a dropped fit's model (its train rows and duals) is still alive"
    finally:
        gc.enable()
