"""Pages of index keys the decode scan fetched over what the XLA scan
fetches at the same steps, summed over the window's launches: counted on the
device a layer a step (``models.dsa_lm``'s ``index_pages_read`` and
``index_pages_padded``, ``ops.dsa_index.pages_read``) and carried on each
launch's ``serving.launch.fold`` span. 1.0 where the XLA scan ran; on the
paged kernel, the share of the XLA scan's pages that it still reads. Nothing
where the program counts no pages."""

from benchmark import phase_readers


def read(run):
    folds = [
        s.attrs for s in phase_readers.run_spans(run)
        if s.name == "serving.launch.fold" and "index_pages_padded" in s.attrs
    ]
    padded = sum(a["index_pages_padded"] for a in folds)
    return sum(a["index_pages_read"] for a in folds) / padded if padded else None
