"""Staging ``in_memory``: rows already in final form become the dataset's
Arrow blocks; the ETL engine and the cluster are bypassed."""


def stage(columns: dict, staging: dict, seed: int):
    import pyarrow as pa

    from raydp_tpu.data.ml_dataset import MLDataset

    table = pa.table(columns)
    n = staging.get("blocks", 1)
    step = -(-table.num_rows // n)
    blocks = [table.slice(i * step, step) for i in range(n)]
    return MLDataset(
        blocks, num_shards=staging.get("shards", 1), shuffle=True,
        shuffle_seed=seed,
    )
