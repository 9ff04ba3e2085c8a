"""Staging ``etl_select``: the cell's own small ETL on the cluster's CPU
workers, as ``chip_smoke.run_train``: drop invalid rows, derive the label,
project, one exchange; then the hand-off ``fit_on_df`` makes. Needs
``workers`` in the staging group (the job starts the cluster)."""


def stage(columns: dict, staging: dict, seed: int):
    import pandas as pd

    import raydp_tpu.dataframe as rdf
    from raydp_tpu.data.ml_dataset import MLDataset
    from raydp_tpu.dataframe import col

    features = [c for c in columns if c not in ("marker", "valid")]
    df = (
        rdf.from_pandas(
            pd.DataFrame(columns), num_partitions=staging["partitions"]
        )
        .filter(col("valid") == 1)
        .withColumn("label", col("marker"))
        .select(*features, "label")
        .repartition(staging["partitions"])
    )
    return MLDataset.from_df(
        df, num_shards=staging["shards"], shuffle=True, shuffle_seed=seed
    )
