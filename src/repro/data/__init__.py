from .paper_datasets import clustered_set, similarity_query, synthetic_dataset, uniform_set
